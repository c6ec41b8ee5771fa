"""OLMo-1B (arXiv:2402.00838): the shared pre-norm decoder (``_decoder``)
with attention and SwiGLU MLP blocks, non-parametric layer norm, rotary
embeddings and a tied head."""
from bench.models._decoder import embed, head, layer, make  # noqa: F401

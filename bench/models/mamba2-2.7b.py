"""Mamba-2-2.7B (arXiv:2405.21060): the shared pre-norm decoder
(``_decoder``) with SSD blocks and no MLP, RMSNorm and a tied head."""
from bench.models._decoder import embed, head, layer, make  # noqa: F401

"""One model module per configuration, ``<config>.py``, found by the
configuration's name in ``BENCHMARK.json`` (``bench.harness.model_module``).
Each supplies the weights and the reference's model-specific equations:

- ``make(key, m, dtype)``: every weight, in the program's layout;
- ``embed(p, tokens, m)``: the embedding rows, float32;
- ``layer(p, x, m, kind, quant)``: one layer of the pattern's ``kind``;
- ``head(params, x, m, quant)``: final norm and output logits.

``m`` is the configuration file's ``model`` block; ``_decoder`` holds the
pieces the modules share.
"""

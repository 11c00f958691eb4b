"""Model blocks the benchmark can serve, one package each, found by name.

A configuration file names its block (``"block": "dense"``) and the harness
imports ``bench/blocks/<block>/``. A block package holds:

  ``__init__.py``  ``geometry(cfg, max_len)``: the published keys as a
                   frozen, hashable ``Geometry`` with at least ``name``,
                   ``layers``, ``d_model``, ``vocab``, ``heads``,
                   ``kv_heads`` and ``head_dim``; refuses what the block
                   does not model. ``kv_bytes_per_token(g)``,
                   ``layer_matmul_params(g)`` (weights one token
                   multiplies by in one layer) and ``model_config(g,
                   dtype)``, the program's ``ModelConfig`` for it.
  ``weights.py``   ``seed_words(seed)`` and ``make_params(g, lo, hi)``:
                   weights from the seed in the program's layout.
  ``reference.py`` ``forward_stats(g, seed, tokens, asked, quant=None)``:
                   the plain float32 reference, importing nothing of the
                   program, and its ``quant="fp8"`` control.

A new kind of model is a new package here; nothing else changes.
"""

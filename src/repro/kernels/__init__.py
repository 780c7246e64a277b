"""Pallas kernels — OPTIONAL layer for the repo's compute hot-spots.

Three kernels, each with an interpret-mode CPU fallback selected
automatically off-TPU (``ops._default_interpret``) so every code path
runs — and is tested — on plain CPU CI, while TPU gets the compiled
program:

* ``flash_attention.py`` — blocked online-softmax attention over
  (b·kv·g, s, hd) lanes (causal / windowed / softcapped); public entry
  ``ops.flash_attention``. Fallback: the same math as a jnp reference
  (``ref.py``) validated bit-close in tests/test_kernels.py.
* ``flash_decode.py`` — single-position KV-cache decode attention,
  split-K over cache blocks; public entry ``ops.flash_decode``.
* ``ssd_scan.py`` — chunked state-space (SSD) scan over (b·h, l, p)
  with grouped B/C; public entry ``ops.ssd``.

Add further kernels ONLY for hot-spots the paper itself optimizes.
"""

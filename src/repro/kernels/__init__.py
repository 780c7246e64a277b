"""Pallas kernels — OPTIONAL layer for the repo's compute hot-spots.

Four kernels, each with an interpret-mode CPU fallback selected
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
* ``round_step.py`` — the fused round-step of the event-rounds sweep
  engine (``repro.sim.rounds``): window compaction, job admission,
  size classes and the unrolled ``compact_every`` event rounds as ONE
  kernel per (point × trace) lane, selected via
  ``ScanOptions(kernel="pallas")``. No separate reference module: the
  kernel body calls the engine's own ``_chunk_core``, so the unfused
  engine IS the reference (``round_step.chunk_step_ref``), bit-identical
  rows by construction (tests/test_round_step_kernel.py). The exception
  to the compiled-on-TPU rule: Mosaic cannot lower this body, so on a
  TPU it raises ``NotImplementedError`` (tests/test_tpu_compile.py).

Add further kernels ONLY for hot-spots the paper itself optimizes.
"""

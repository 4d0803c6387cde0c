"""Pallas TPU kernels for the perf-critical compute layers, each shipped as
``kernels/<name>/{<name>.py, ops.py, ref.py}``:

* ``flash_attention`` — blocked causal/windowed/softcapped attention (prefill).
* ``decode_attention`` — flash-decoding style single-token attention over a
  (possibly sequence-sharded) KV cache.
* ``wkv6`` — RWKV-6 chunked recurrence with data-dependent decay.

``ops.py`` is the dispatching wrapper: Pallas on the TPU, blocked XLA
elsewhere (``backend.get_backend``); ``ref.py`` is the pure-jnp oracle used
by the allclose test sweeps.  On CPU the kernels are validated with
``interpret=True`` and compiled for a described TPU v5e in
``tests/test_tpu_compile.py``.
"""
from repro.kernels.backend import get_backend, use_backend  # noqa: F401

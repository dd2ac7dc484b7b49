"""lighthouse_tpu — a TPU-native Ethereum consensus-layer framework.

A ground-up rebuild of the capabilities of the reference client (Lighthouse,
``/root/reference``): SSZ types and the beacon state transition, fork choice, batched
signature-verification pipelines, a back-pressured scheduler, storage, networking,
validator client, and HTTP APIs — with the BLS12-381 batch-verification hot path
executed as JAX/XLA kernels on TPU.

Importing this package enables 64-bit JAX types: the big-integer limb kernels
accumulate 16-bit-limb products in uint64 lanes.
"""

try:
    import os as _os

    import jax as _jax

    _jax.config.update("jax_enable_x64", True)
    # Persistent XLA compilation cache: the BLS kernels are large programs and
    # warm runs (tests, benches, the chain) must not re-pay compilation. JAX
    # reads JAX_COMPILATION_CACHE_DIR itself; without it the cache sits at a
    # fixed path in the checkout (the path is part of the key, so a moving
    # directory never hits). Opt out with LIGHTHOUSE_TPU_NO_JIT_CACHE=1.
    if not _os.environ.get("LIGHTHOUSE_TPU_NO_JIT_CACHE"):
        if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # Partition by host CPU fingerprint: XLA:CPU AOT executables
            # compiled for another host's feature set abort at run time
            # (cpu_aot_loader SIGILL warning).
            from .native.build import host_tag as _host_tag

            _jax.config.update(
                "jax_compilation_cache_dir",
                _os.path.join(
                    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
                    ".jax_cache",
                    _host_tag(),
                ),
            )
        _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
        _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # installs the compile-phase listener (jax_compile_phase_seconds)
    from .utils import tracing as _tracing  # noqa: F401
except ImportError:  # the pure-Python oracle backend works without jax
    pass

__version__ = "0.2.0"

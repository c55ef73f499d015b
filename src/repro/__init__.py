"""NanoZK-TPU: layerwise zero-knowledge proofs for verifiable LLM inference.

The package enables JAX's persistent compilation cache on import: the
prover and verifier lean on many small jitted field kernels whose compiles
dominate cold starts.  ``JAX_COMPILATION_CACHE_DIR`` places the cache when
it is set (JAX reads it itself); otherwise the cache lives at a fixed
directory inside the checkout, ``<repo>/.jax_cache``, so that every run of
the same checkout finds it again.
"""
import os

import jax

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
# JAX-level cache only: XLA:CPU AOT artifacts warn about machine feature
# mismatches under the jemalloc preload wrapper.
jax.config.update("jax_persistent_cache_enable_xla_caches", "none")

"""repro — Mixed-Kernel Mixed-Signal SVMs for Flexible Electronics, in JAX.

A production-grade JAX framework reproducing and extending
"Design and Optimization of Mixed-Kernel Mixed-Signal SVMs for Flexible
Electronics" (Afentaki et al., 2025), plus the multi-pod LM substrate for the
assigned architecture pool (see DESIGN.md).

Subsystems:

  repro.api          public estimator + compiled-machine API (MixedKernelSVM,
                     compile_machine) — start here
  repro.core         paper's contribution (SVM, analog model, selection, cost)
  repro.data         datasets + token pipeline
  repro.models       LM architectures
  repro.training     optimizer / train_step
  repro.serving      KV cache / prefill / decode
  repro.distributed  sharding rules, mesh utils, PP, elastic, compression
  repro.checkpoint   fault-tolerant checkpointing
  repro.kernels      Pallas TPU kernels (+ refs)
  repro.configs      architecture configs
  repro.launch       mesh / dryrun / train / serve entrypoints
  repro.obs          compile counters (jax.monitoring), registered on import
"""

from repro import obs  # noqa: F401  (registers the compile counters)

__version__ = "1.1.0"

_API_EXPORTS = ("MixedKernelSVM", "CompiledMachine", "compile_machine")


def __getattr__(name):
    """Lazy re-export of the public API (keeps `import repro` light)."""
    if name in _API_EXPORTS:
        from repro import api

        return getattr(api, name)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")

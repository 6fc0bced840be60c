"""apex_tpu — a TPU-native training-acceleration framework.

A brand-new, idiomatic JAX/XLA/Pallas framework with the capabilities of
NVIDIA Apex (reference: CodeFisheng/apex).  Where the reference ships CUDA
kernels (``csrc/``), NCCL process groups (``apex/parallel``,
``apex/transformer``) and torch monkey-patching (``apex/amp``), this framework
ships Pallas TPU kernels, a single named ``jax.sharding.Mesh``, and a
functional precision-policy layer.

Subpackages
-----------
- :mod:`apex_tpu.parallel_state` — mesh / axis registry
  (≙ ``apex/transformer/parallel_state.py``).
- :mod:`apex_tpu.ops` — fused ops: LayerNorm/RMSNorm, scaled masked softmax,
  RoPE, softmax-xentropy, flash attention (≙ ``csrc/``, ``apex/normalization``,
  ``apex/contrib/{xentropy,multihead_attn,fmha}``).
- :mod:`apex_tpu.optimizers` — fused multi-tensor optimizers
  (≙ ``apex/optimizers``, ``csrc/multi_tensor_*``).
- :mod:`apex_tpu.amp` — precision policies + dynamic loss scaling
  (≙ ``apex/amp``, ``apex/fp16_utils``).
- :mod:`apex_tpu.parallel` — data parallelism + SyncBatchNorm + LARC
  (≙ ``apex/parallel``).
- :mod:`apex_tpu.transformer` — tensor/sequence/pipeline parallelism
  (≙ ``apex/transformer``).
- :mod:`apex_tpu.contrib` — contrib parity layer (≙ ``apex/contrib``).
- :mod:`apex_tpu.models` — reference models used by the benchmark configs
  (BERT-Large, GPT, ResNet-50).
- :mod:`apex_tpu.checkpoint` — sharded save/restore + step-numbered
  checkpoint management (orbax-backed).
- :mod:`apex_tpu.resilience` — fault injection, guarded steps,
  retry/backoff, and the preemption-safe auto-resume loop.
- :mod:`apex_tpu.observability` — unified step telemetry: device-side
  metric registry, MFU/goodput meters, JSONL/CSV/TensorBoard export,
  and scheduled trace windows.
- :mod:`apex_tpu.analysis` — jaxpr/HLO graph linter: transfer /
  promotion / donation / retrace / collective-consistency passes over
  traced and compiled step programs.
- :mod:`apex_tpu.train` — the single composable training entry point:
  a declarative dp×tp trainer with framework-chosen (ZeRO-style)
  update sharding, self-verified against the analysis passes at build.
- :mod:`apex_tpu.serve` — AOT-compiled serving: paged KV cache,
  continuous batching, TTFT SLOs.
"""

__version__ = "0.1.0"

# Light-weight eager imports only; heavy subpackages are imported lazily so
# `import apex_tpu` stays cheap (the reference's `apex/__init__.py` likewise
# defers contrib imports behind availability probes).
from apex_tpu import parallel_state  # noqa: F401

_LAZY_SUBMODULES = (
    "analysis",
    "ops",
    "optimizers",
    "amp",
    "parallel",
    "transformer",
    "contrib",
    "models",
    "fp16_utils",
    "normalization",
    "mlp",
    "fused_dense",
    "checkpoint",
    "resilience",
    "observability",
    "serve",
    "train",
)


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib

        try:
            module = importlib.import_module(f"apex_tpu.{name}")
        except ModuleNotFoundError as e:
            # PEP 562: availability probes (hasattr/getattr) must see
            # AttributeError, mirroring the reference's per-feature
            # try-import probing in apex/contrib/*/__init__.py.
            raise AttributeError(
                f"module 'apex_tpu' has no attribute {name!r}"
            ) from e
        globals()[name] = module
        return module
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals().keys()) + list(_LAZY_SUBMODULES))

"""Pallas TPU kernels for the framework's compute hot spots.

  flash_attention/  — blocked online-softmax attention (GQA, causal):
                      the train/prefill hot spot of every attention arch.
  linear_scan/      — chunked diagonal-decay state scan: the Mamba/RWKV6
                      recurrence (jamba, rwkv6 at 500k context).
  maxplus/          — (max,+)-semiring blocked mat-vec: the LLAMP DAG
                      engine's level-relaxation inner loop for dense-banded
                      execution graphs (parameter sweeps batch over the
                      lane dimension).

Kernels are written against TPU BlockSpec/VMEM tiling and compile for
the TPU; on the CPU backend they run in ``interpret=True`` mode (tests).
``maxplus/ops.py`` resolves that in one place and raises on any other
platform; ``tests/test_tpu_compile.py`` compiles every maxplus entry point
for a described v5e chip.
"""

from .flash_attention.ops import flash_attention  # noqa: F401
from .linear_scan.ops import linear_scan  # noqa: F401
from .maxplus.ops import maxplus_matvec  # noqa: F401

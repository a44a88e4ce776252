"""TPU ops: pallas kernels for the hot paths.

The models in `symbiont_tpu.models` are pure XLA by default (XLA's fusion
already covers most of what hand scheduling would buy); this package holds the
kernels where a pallas implementation beats stock XLA — attention
(`flash_attention`), the FLOPs center of every forward in the zoo and the
direct descendant of the reference's one compute core (reference:
services/preprocessing_service/src/embedding_generator.rs:198), and the
routed experts' grouped matmul (`grouped_matmul`), whose groups are smaller
than the row tile the compiler gives `ragged_dot`.
"""

from symbiont_tpu.ops.flash_attention import flash_attention
from symbiont_tpu.ops.grouped_matmul import grouped_matmul

__all__ = ["flash_attention", "grouped_matmul"]

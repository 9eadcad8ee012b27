"""Distribution layer: sharding, compressed collectives, multi-device step,
pipeline parallelism and error feedback.

Modules (import explicitly; only the lightweight ones load eagerly):

* :mod:`~repro.dist.actx` — logical-axis activation constraints (used by the
  models; passthrough outside a ``use_mesh`` scope).
* :mod:`~repro.dist.sharding` — (name, rank)-keyed PartitionSpec rules for
  params / optimizer state / batches / KV caches.
* :mod:`~repro.dist.collectives` — takum-compressed ring all-reduce
  (``compressed_psum``) + the analytic wire-traffic model.
* :mod:`~repro.dist.step` — sharded train/prefill/serve step builders.
* :mod:`~repro.dist.pipeline` — GPipe-style microbatched stage execution.
* :mod:`~repro.dist.error_feedback` — residual-carrying compressed psum.

``step`` and ``sharding`` are *not* imported here to keep the models ->
actx -> dist import chain acyclic (step imports the models).
"""

from . import actx

__all__ = ["actx"]

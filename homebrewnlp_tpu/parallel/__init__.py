"""Parallelism building blocks: sequence/context parallelism (ring attention)
and mesh helpers.  The reference has NO sequence parallelism (SURVEY.md §5.7)
— long context there leans on reversible blocks only; here the sequence dim is
a first-class mesh axis.  Beside them the Pallas TPU kernels, one module a
mechanism: ``flash_attention`` (causal, windowed, block- and key-selected),
``map_mixer``, ``causal_conv``, ``ssd_scan``, ``delta_solve``, ``delta_rule``,
``kda_rule`` and ``index_loss`` (the learned indexer's loss pass)."""
from .ring_attention import ring_attention  # noqa: F401

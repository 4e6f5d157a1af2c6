"""The flash-attention kernels' share of their roofline, percent, where
window-4,096 layers with rotary stand 3 : 1 beside global layers without
positions at 28 query / 4 K/V heads: each call costed by its own kind
(``roofline/smallthinker_costs.py flash_cost``, which is
``laguna_costs.flash_cost`` read through this configuration's layer strings) —
a ``flash_*_window`` call over the BAND's live pairs, a ``flash_*_causal``
call over the triangle's, forward and backward — by the reader of the other
window + global cell (``laguna_flash_roofline.read``: the least time the chip
could take for all calls, the larger of required operations over the peak
FLOP/s and bytes over the peak bytes/s, over the time they took).  It cannot
pass 100: the kernels run at least the live pairs' matmuls (the masked parts
of their edge tiles on top) and move at least the counted tensors once.  The
notes give each kind, which is where the windowed forward's form (band or
tiled: the gauge ``hbnlp_flash_band_layers`` says which) shows its cost a
call."""
from ..lib import program_readers
from ..roofline import smallthinker_costs
from . import laguna_flash_roofline

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if not smallthinker_costs.early_routers(run.config):
        return None
    value = laguna_flash_roofline.read(run)
    if value is not None:
        run.notes.append(
            "hbnlp_flash_band_layers "
            f"{program_readers.counter(run, 'hbnlp_flash_band_layers')}")
    return value

"""The map-mixer kernels' share of their roofline, percent."""
from ..lib import readers

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return readers.kernel_roofline(run, r"^map_mixer_")

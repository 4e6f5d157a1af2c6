"""Device time of the map-mixer Pallas kernels over the device's busy time,
percent."""
from ..lib import readers

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return readers.kernel_time_share(run, r"^map_mixer_")

"""The whole model step's share of the chip's int8 peak: model ops per
sample (2 x MAC of every conv and dense layer, ``bench/workcount.py``)
times the samples completed per second in the untraced window, over
the published int8 peak of the device."""
from bench import workcount

SOURCE = "host_clock"
LAYER = "model step"
MOVES = "samples_per_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    if run.peak is None or run.samples_per_s <= 0:
        return None
    return (100.0 * workcount.model_ops(run.layers) * run.samples_per_s
            / run.peak["int8_ops_per_s"])

"""The whole CNN step's share of the chip's int8 peak: 2 x the layer
table's multiply-accumulates per image x images served in the traced
window, over the window times 393 TOP/s, in %."""
from bench import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ops = 2.0 * work.cnn_macs(run.layers) * run.images_traced
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops"])

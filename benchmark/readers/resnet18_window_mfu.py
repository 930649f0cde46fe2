"""FLOPs of the rows the timed fit trained (E x rows an epoch, forward and
backward by layer shapes) over what the chips could do in the call's
length at their peak."""

from lib import counts
from lib.peaks import peaks


def read(run):
    if run.peak.get("platform") != "tpu" or run.fit_s <= 0:
        return None
    flops = counts.resnet18_train_flops_per_row(run.cfg) * run.epochs * run.rows_per_epoch
    return 100.0 * flops / (run.fit_s * peaks(run.peak["kind"])["flops_per_s"] * run.cell.chips)

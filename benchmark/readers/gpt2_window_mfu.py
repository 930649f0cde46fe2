"""FLOPs that the tokens committed in the window needed (every prefill
chunk's valid tokens, every decode step's lanes) over what the chips could
do in the window's length at their peak."""

from lib import counts, serve
from lib.peaks import peaks


def read(run):
    if run.window is None or run.peak.get("platform") != "tpu":
        return None
    steps, chunks = serve.lengths_before_each_step(run, run.window.t_open, run.window.t_close)
    flops = sum(counts.gpt2_decode_cost(run.cfg, lengths)[0] for lengths in steps) + \
        sum(counts.gpt2_chunk_cost(run.cfg, start, valid)[0] for start, valid in chunks)
    if flops <= 0:
        return None
    peak = peaks(run.peak["kind"])["flops_per_s"] * run.cell.chips
    return 100.0 * flops / (run.window.seconds * peak)

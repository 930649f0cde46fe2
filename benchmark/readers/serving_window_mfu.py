"""FLOPs that the tokens committed in the window needed (every prefill
chunk's valid tokens, every decode step's lanes, by the family's cost
functions) over what the chips could do in the window's length at their
peak. The chunks' (start, valid) come from the traced run's spans; where
they are not the chunks the `step` events counted, there is nothing sound
to read."""

from lib import serve
from lib.peaks import peaks


def read(run):
    if run.window is None or run.peak.get("platform") != "tpu":
        return None
    steps, chunks = serve.lengths_before_each_step(run, run.window.t_open, run.window.t_close)
    if len(chunks) != run.window.chunk_calls:
        return None
    flops = sum(run.family.decode_cost(run.cfg, lengths)[0] for lengths in steps) + \
        sum(run.family.chunk_cost(run.cfg, start, valid)[0] for start, valid in chunks)
    if flops <= 0:
        return None
    run.extra.setdefault("needed", {})["window_flops"] = flops
    peak = peaks(run.peak["kind"])["flops_per_s"] * run.cell.chips
    return 100.0 * flops / (run.window.seconds * peak)

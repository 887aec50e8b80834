"""The histogram kernel's share of its roofline, in percent.

The least time the chip could take for the kernel's calls in the window
(`lib/opcount.hist_contraction` against the published peaks) over the time
the trace says they took.  Slots, statistic planes and the planes' type are
read from each call's own instruction text,

    %hist_build.16 = f32[8192,125] custom-call(u8[124,32,8192] bins,
        bf16[124,5,8192] stats, s32[124,1,8192] leaf ids, s32[25,1] slots)

while rows, features and bins are the configuration's, not the padded ones.
The contraction feeds the MXU bf16 operands, so the compute peak is the
bf16 one.  Which bound holds goes on an earlier line.
"""

import re

from benchmarks.lib import opcount, peaks

_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4}


def call_shape(text: str):
    """(slots, planes, bytes of a statistic) of one kernel call, or None
    where the instruction is not the call this reader knows."""
    shapes = [(t, [int(d) for d in dims.split(",")]) for t, dims
              in _SHAPE.findall(text.split("custom_call_target")[0])]
    if len(shapes) != 5 or "custom-call(" not in text:
        return None
    (_, out), _, (stat_type, stats), _, (_, slots) = shapes
    if (len(out) != 2 or len(stats) != 3 or stat_type not in _BYTES
            or out[1] != slots[0] * stats[1]):
        return None
    return slots[0], stats[1], _BYTES[stat_type]


def read(run):
    hist = run.cell.load("layer_metrics", "hist_build_ms_per_iter")
    facts = run.facts
    ops = byts = seconds = 0.0
    for ev in hist.events(run):
        for name, dur in zip(ev.names, ev.dur):
            shape = call_shape(name)
            if shape is None:
                return None
            slots, planes, stat_bytes = shape
            o, b = opcount.hist_contraction(
                facts["rows"], facts["features"], facts["bins"], slots,
                planes, stat_bytes=stat_bytes)
            ops, byts, seconds = ops + o, byts + b, seconds + float(dur)
    if not seconds:
        return None
    peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    share, bound = opcount.roofline(ops, byts, seconds, peak["bf16_flops"],
                                    peak["hbm_bytes_per_s"])
    run.cell.say("hist_kernel_roofline", bound=bound, kernel_s=seconds,
                 operations=ops, bytes=byts)
    return share

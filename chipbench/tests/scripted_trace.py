"""A scripted trace of two chips, small enough to check by hand.

Chip 0, in microseconds: program "step(1)" runs [0, 100),
[200, 300) and [400, 500); inside each run: conv [0, 40), all-reduce [40, 60) with
nothing beside it, fusion [60, 100). Chip 1 runs the same program 10 us
longer, its all-reduce [40, 70) overlapped by a copy [50, 70). A small
program "poke(2)" runs three times for 2 us. The host thread is inside
"dispatch" over the first gap and inside "sample" over half the second.
"""


def _e(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_us * 1000), "dur_ns": int(dur_us * 1000)}


def events():
    out = []
    for chip, extra in ((0, 0), (1, 10)):
        plane = f"/device:TPU:{chip}"
        for base in (0, 200, 400):
            out.append(_e(plane, "XLA Modules", "step(1)", base,
                          100 + extra))
            out.append(_e(plane, "XLA Ops", "conv.1", base, 40))
            out.append(_e(plane, "XLA Ops", "all-reduce.7", base + 40,
                          20 + extra))
            if chip == 1:
                out.append(_e(plane, "XLA Ops", "copy.3", base + 50, 20))
            out.append(_e(plane, "XLA Ops", "fusion.2", base + 60 + extra,
                          40))
        for k in range(3):
            out.append(_e(plane, "XLA Modules", "poke(2)", 120 + 20 * k, 2))
            out.append(_e(plane, "XLA Ops", "scatter.9", 120 + 20 * k, 2))
    out.append(_e("/host:CPU", "main", "chipbench.window", 0, 600))
    out.append(_e("/host:CPU", "sched", "dispatch", 95, 110))
    out.append(_e("/host:CPU", "sched", "sample", 300, 60))
    return out

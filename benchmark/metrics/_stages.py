"""Shared arithmetic of the OSD stage metrics: self-seconds of the named
stages over the window (critical-path self-time, as the OSDs' stage
histograms sum it exactly), per OSD client op, in milliseconds."""


def per_op_ms(w, match):
    win = w["window"]["osd"]
    if win["ops"] <= 0:
        return None
    total = sum(s for stage, s in win["stage_s"].items() if match(stage))
    return total / win["ops"] * 1e3

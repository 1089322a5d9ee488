"""Shared arithmetic of the dispatch-stage metrics: seconds of the
named ``dispatch_*`` stages over the window, per batched encode+CRC
dispatch, in milliseconds.  The encode service feeds each batch's
seven stages (they divide its ``dispatch_seconds``) to its OSD's stage
histograms, which the window sums as ``osd.stage_s``; batches are the
``encode_hinfo`` profiles' count, as ``encode_dispatch_ms`` divides
by.  The first stage named must be there: a program that records no
dispatch stages has nothing to read."""


def per_batch_ms(w, first, *more):
    win = w["window"]
    stage_s = win["osd"]["stage_s"]
    batches = win["encode"]["batches"]
    if first not in stage_s or batches <= 0:
        return None
    total = stage_s[first] + sum(stage_s.get(s, 0.0) for s in more)
    return total / batches * 1e3

"""The model work a serving window's requests asked for inside a time
interval, from the requests' own host-clock stamps and the byte and flop
functions of ``lib/flops.py`` — the numerators of the serving MFU and
roofline readers.  Nothing here reads the program's own byte gauges.
"""
from benchmark.lib import flops


def in_interval(requests, m, a, b):
    """Work between host times ``a`` and ``b`` (perf_counter seconds).

    Decode: tokens 2..n of a request are taken as evenly spaced between its
    ``t_first`` and ``t_done``; token j (1-based after the first) is decoded
    against ``prompt + j`` cached rows.  Prefill: a request's prompt is
    attributed by the share of its ``prefilling`` timeline marks (one per
    chunk) that fall inside the interval."""
    w = {"decode_tokens": 0, "decode_flops": 0.0, "decode_context_rows": 0,
         "prefill_tokens": 0.0, "prefill_flops": 0.0, "prefill_chunks": 0}
    for r in requests:
        p, n = len(r.prompt_ids), len(r.output_ids)
        marks = [x["t"] for x in r.timeline() if x["phase"] == "prefilling"]
        inside = sum(a <= t < b for t in marks)
        if inside:
            share = inside / len(marks)
            w["prefill_chunks"] += inside
            w["prefill_tokens"] += share * p
            w["prefill_flops"] += share * flops.prefill_flops(
                m, p, 0, with_head=True)
        if r.t_first is None or n < 2:
            continue
        t_end = r.t_done if r.t_done is not None else b
        gap = (t_end - r.t_first) / (n - 1)
        for j in range(1, n):
            t = r.t_first + j * gap
            if a <= t < b:
                w["decode_tokens"] += 1
                w["decode_context_rows"] += p + j
                w["decode_flops"] += flops.decode_token_flops(m, p + j)
    return w

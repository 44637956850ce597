"""Share of its roofline that the prefill program reaches, in percent:
the least time the chip could take for the prefills that ran in the traced
window (the larger of FLOPs over peak and bytes over bandwidth, from
the family's ``prefill`` in ``bench/arch/<arch>.py``) over their device time in the profiler trace.  Each
device run of the program is paired with the engine's prefill op, which
says its request, and so its prompt length."""
from bench import arch, counts, trace

MODULE = "jit__prefill_step"


def read(run):
    if run.device is None or run.peak is None:
        return None
    a = arch.of(run.model)
    by_id = {r.req_id: r for r in run.requests}
    least = dev = 0.0
    for ev, secs in trace.match(run.device, MODULE, run.timeline_ops(
            "prefill")):
        r = by_id.get(ev["args"].get("req_id"))
        if r is None:
            continue
        least += counts.least_time(*a.prefill(run.model, r.prompt_len),
                                   run.peak)
        dev += secs
    return 100.0 * least / dev if dev > 0 else None

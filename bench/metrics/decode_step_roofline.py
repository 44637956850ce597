"""Share of its roofline that the decode program reaches, in percent: the
least time of the decode steps that ran in the traced window over their
device time in the profiler trace.  Each device run is paired with the
engine's decode op; the step's sequences and their context lengths come
from the token times, which the engine stamps alike for every sequence of
one step, just after the op completes."""
import bisect

from bench import arch, counts, trace
from bench.records import decode_steps

MODULE = "jit__decode_step"


def read(run):
    if run.device is None or run.peak is None:
        return None
    a = arch.of(run.model)
    steps = decode_steps(run.requests)
    times = sorted(steps)
    least = dev = 0.0
    for ev, secs in trace.match(run.device, MODULE, run.timeline_ops(
            "decode")):
        done = (ev["ts"] + ev["dur"]) * 1e-6
        i = bisect.bisect_left(times, done)
        if i == len(times) or times[i] - done > 0.5:
            continue
        ctx = [c for _, c in steps[times[i]]]
        least += counts.least_time(*a.decode_step(run.model, ctx),
                                   run.peak)
        dev += secs
    return 100.0 * least / dev if dev > 0 else None

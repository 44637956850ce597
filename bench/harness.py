"""One run of one cell: set-up, the measured window, the check, the metrics.

The system under test is ``repro.serving.engine.RealEngine``; the harness
builds it from a configuration file, warms up every shape the cell's mix
can send, then offers the mix through ``RealEngine.submit``, each request
at its due time with ``arrival_time`` preset to that time.  Every token's
time is kept (``records.TimedRequest``).  After the window the sample that
``bench.check`` draws is compared with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import importlib.util
import json
import os
import queue
import sys
import threading
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
TRACE_S = 12.0          # longest device trace a traced run takes
STOP_WAIT_S = 120.0     # the most a run waits for the engine to empty
WARM_UP_INDEX = 1 << 30  # prompt ids of warm-up requests, apart from traffic
WARM_UP_WAIT_S = 300.0   # the most one warm-up request may take (compiling)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """The ``read(run)`` function of ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Times of JAX's lowerings, from its monitoring events: each is a new
    executable, compiled or loaded from the persistent cache."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, jax):
        self.times: List[float] = []
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                if event == self.EVENT:
                    self.times.append(time.monotonic())


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: str
    config: Dict
    model: Dict
    mix: Dict
    chips: int
    peak: Optional[Dict]
    w0: float
    w1: float
    closed: float                  # end of the wait for first tokens
    requests: list
    compiles: List[float]
    timeline: Optional[List[dict]] = None
    device: object = None          # trace.DeviceTrace of the traced run
    setup_s: float = 0.0

    def timeline_ops(self, phase: str) -> List[dict]:
        return [e for e in (self.timeline or [])
                if e["name"] == f"{phase}:launch"]


def peak_for(kind: str) -> Dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


def use_compile_cache(jax) -> str:
    """The program's persistent cache (``<checkout>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` says), with every program cached."""
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Cell:
    """A cell's files and its engine, set up once per process."""

    def __init__(self, cell: str, config_path: str, mix_path: str,
                 seed: int, *, chips_needed: int = 1,
                 require_tpu: bool = True, cache: bool = True,
                 trace_dir: Optional[str] = None):
        import jax
        from bench import arch, traffic, weights
        self.jax = jax
        self.cell = cell
        self.config = load_json(config_path)
        self.model_spec = self.config["model"]
        self.mix = traffic.load_mix(mix_path)
        self.seed = int(seed)
        devs = jax.devices()
        if require_tpu and devs[0].platform != "tpu":
            raise SystemExit(f"bench: JAX finds no TPU (platform "
                             f"{devs[0].platform!r})")
        if len(devs) < chips_needed:
            raise SystemExit(f"bench: the cell needs {chips_needed} chips, "
                             f"JAX finds {len(devs)}")
        self.devices = devs
        self.chips = chips_needed
        self.peak = peak_for(devs[0].device_kind) if require_tpu else None
        if cache:
            log(f"compile cache {use_compile_cache(jax)}")
        self.compiles = CompileLog(jax)
        self.thread_errors: list = []
        hook = threading.excepthook

        def on_thread_error(a):
            self.thread_errors.append(a)
            hook(a)
        threading.excepthook = on_thread_error
        if trace_dir is not None:
            os.makedirs(trace_dir, exist_ok=True)
            os.environ["FLEX_PROFILE"] = "1"
            os.environ["FLEX_PROFILE_DIR"] = trace_dir
        self.trace_dir = trace_dir

        from repro.distributed.sharding import unbox
        from repro.models import build_model
        self.model = build_model(
            arch.of(self.model_spec).program_config(self.config))
        weights.check_layout(
            weights.layout(self.model_spec),
            jax.eval_shape(lambda: unbox(self.model.init(
                jax.random.PRNGKey(0)))))
        self.params = weights.make_params(self.model_spec, self.seed,
                                          devs[0])
        self.engine = self._engine()
        self.grid = traffic.prompt_grid(self.mix)

    def _engine(self):
        from repro.serving.engine import RealEngine
        e = self.config["engine"]
        return RealEngine(self.model, self.params, mode=e["mode"],
                          max_num_seqs=e["max_num_seqs"],
                          max_len=e["max_len"],
                          replicas=e.get("replicas", 1))

    # ------------------------------------------------------------ requests
    def request(self, index: int, prompt_len: int, out_len: int,
                due: float, span=None):
        from bench import traffic
        from bench.records import TimedRequest
        toks = traffic.prompt_tokens(self.seed, index, prompt_len,
                                     self.model_spec["vocab_size"])
        return TimedRequest(prompt_len=prompt_len, max_new_tokens=out_len,
                            prompt_tokens=toks, arrival_time=due, due=due,
                            wanted=out_len, span=span)

    def wait_idle(self, timeout: float) -> bool:
        end = time.monotonic() + timeout
        while self.engine.outstanding > 0:
            self.check_threads()
            if time.monotonic() > end:
                return False
            time.sleep(0.01)
        return True

    def warm_up(self) -> None:
        """Run every prompt length of the mix's grid once, one after the
        other, with a decode step after each, so that every program the
        window uses exists."""
        for i, n in enumerate(self.grid):
            self.engine.submit(self.request(WARM_UP_INDEX + i, n, 2,
                                            time.monotonic()))
            if not self.wait_idle(WARM_UP_WAIT_S):
                raise RuntimeError(f"warm-up of a {n}-token prompt did not "
                                   f"finish in {WARM_UP_WAIT_S} s")
        self.engine.finished.clear()

    def check_threads(self) -> None:
        """Raise if a thread of the engine died: it would hang the run."""
        if self.thread_errors:
            a = self.thread_errors[0]
            raise RuntimeError(f"engine thread {a.thread.name} died: "
                               f"{a.exc_type.__name__}: {a.exc_value}")

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[:self.chips]]
        return int(max(peaks))

    def close(self) -> None:
        """Shut the engine down and free the program's state."""
        if self.engine is not None:
            self.engine.shutdown()
        self.engine = None
        self.params = None
        self.model = None
        gc.collect()


def stop(cell: Cell, reqs) -> float:
    """End every request at its next token, wait for the engine to empty,
    and return the time the stop was made: requests finished before it
    ran to their own end."""
    t = time.monotonic()
    for r in reqs:
        if r.state.value not in ("done", "failed", "rejected"):
            r.max_new_tokens = max(r.generated, 1)
    if not cell.wait_idle(STOP_WAIT_S):
        raise RuntimeError(f"{cell.engine.outstanding} requests still in "
                           f"the engine {STOP_WAIT_S} s after the stop")
    return t


def drive(cell: Cell, seconds: float, *, rate_per_s: float = None,
          trace: bool = False, span=None):
    """Offer the mix: a lead-in, then the window of ``seconds``.  Returns
    (requests, w0, w1, closed, lateness, device trace)."""
    from bench import traffic
    jax = cell.jax
    mix = cell.mix
    planned = traffic.plan(mix, seconds, rate_per_s)
    done_q: "queue.Queue" = queue.Queue()
    cell.engine.on_request_done = lambda r: done_q.put(time.monotonic())
    reqs, lateness = [], []
    t0 = time.monotonic()
    w0 = t0 + mix["lead_in_s"]
    w1 = w0 + seconds
    give_up = w1 + mix.get("drain_s", 30.0)
    tracer = Tracer(jax, cell.trace_dir, w0, min(seconds, TRACE_S)) \
        if trace else None

    def submit(p, due):
        r = cell.request(p.index, p.prompt_len, p.output_len, due, span)
        reqs.append(r)
        late = time.monotonic() - due
        if span is not None:
            with span("bench.submit"):
                cell.engine.submit(r)
        else:
            cell.engine.submit(r)
        lateness.append(late)

    def waiting_for_first_token(now):
        return now < w1 or any(not r.token_times for r in reqs
                               if w0 <= r.due < w1)

    if mix["loop"] == "open":
        for p in planned:
            cell.check_threads()
            due = t0 + p.due
            now = time.monotonic()
            if not waiting_for_first_token(now) or now > give_up:
                break
            if due > now:
                time.sleep(due - now)
            submit(p, due)
    else:
        # closed loop: a client sends its next request when its last ends
        nxt = iter(planned)
        heap = [(t0 + s, i) for i, s in enumerate(traffic.client_starts(mix))]
        heapq.heapify(heap)
        while True:
            cell.check_threads()
            now = time.monotonic()
            if not waiting_for_first_token(now) or now > give_up:
                break
            try:
                while True:
                    heapq.heappush(heap, (done_q.get_nowait(), -1))
            except queue.Empty:
                pass
            if heap and heap[0][0] <= now:
                due, _ = heapq.heappop(heap)
                submit(next(nxt), due)
                continue
            wait = (heap[0][0] - now) if heap else 0.05
            try:
                heapq.heappush(heap, (done_q.get(timeout=min(wait, 0.05)),
                                      -1))
            except queue.Empty:
                pass
    closed = time.monotonic()
    device = tracer.finish() if tracer else None
    return reqs, w0, w1, closed, lateness, device


class Tracer:
    """A ``jax.profiler`` trace of [start, start + seconds], started and
    stopped on a thread of its own so that the generator keeps time."""

    def __init__(self, jax, out_dir: str, start: float, seconds: float):
        self.jax = jax
        self.dir = os.path.join(out_dir, "profile")
        self.t0 = self.t1 = None      # t0: the bench.sync clock reading
        self.error = None
        self._th = threading.Thread(target=self._run,
                                    args=(start, seconds), daemon=True)
        self._th.start()

    def _run(self, start: float, seconds: float):
        jax = self.jax
        try:
            time.sleep(max(0.0, start - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.sync"):
                self.t0 = time.monotonic()
            time.sleep(seconds)
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:   # reported by finish()
            self.error = e

    def finish(self):
        from bench import trace
        self._th.join(timeout=600)
        if self.error is not None:
            raise self.error
        if self._th.is_alive() or self.t1 is None:
            raise RuntimeError("the device trace did not finish")
        return trace.load(self.dir, self.t0, self.t0, self.t1)

#!/usr/bin/env python3
"""Bring-up smoke test: the served path on a TPU at olmo-1b's published width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # replicas and disaggregated pairs

One chip: builds olmo-1b at its published config with random weights drawn
from ``--seed`` and serves one burst of 8 requests (prompts of 128 and 512
tokens, 64 new tokens each) through ``RealEngine`` under ``passthrough``,
``static_colocate`` and ``dynamic_pd`` in turn.  It checks that every
request completes, that the tokens are identical across the three modes,
that each first token equals a plain jitted batch-1 ``model.prefill``, and
that the weights and the slot cache sit on the chip.

Four chips: serves the same burst with ``replicas=4`` under ``dynamic_pd``
and with ``mode="disagg", replicas=2``, and compares both with one-chip
``passthrough``: the same tokens, and each replica's arrays on a chip of
its own.

The latencies it prints are bring-up observations, not benchmark results.
Everything runs in this one process.  Where JAX finds no TPU it exits
non-zero and prints no result.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
from collections import Counter

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "olmo-1b"
PROMPT_LENS = (128, 512)     # one prefill program per distinct length
N_REQUESTS = 8
MAX_NEW = 64
MAX_NUM_SEQS = 8
MAX_LEN = 2048
ONE_CHIP_MODES = ("passthrough", "static_colocate", "dynamic_pd")


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (compiles happen on the engine's daemon threads)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration


def devices_of(tree) -> set:
    import jax
    return set().union(*(x.devices() for x in jax.tree.leaves(tree)))


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LENS[i % len(PROMPT_LENS)]).tolist()
            for i in range(N_REQUESTS)]


def serve(model, params, prompts, mode, clock, *, max_new=MAX_NEW, **kw):
    """One burst through a fresh engine.  Returns the requests, the run
    summary, the compile seconds spent in it and each replica's placement."""
    from repro.serving.engine import RealEngine
    from repro.serving.request import Request
    reqs = [Request(prompt_len=len(p), max_new_tokens=max_new,
                    prompt_tokens=p, arrival_time=0.0) for p in prompts]
    c0 = clock.seconds
    eng = RealEngine(model, params, mode=mode, max_num_seqs=MAX_NUM_SEQS,
                     max_len=MAX_LEN, **kw)
    try:
        res = eng.run(reqs, timeout=900)
        placement = [{"chip_p": rep.chip_p, "chip_d": rep.chip_d,
                      "params_p": devices_of(rep.params_p),
                      "params_d": devices_of(rep.params_d),
                      "slot_cache": devices_of(rep.slot_cache)}
                     for rep in eng.replicas]
    finally:
        eng.shutdown()
    del eng
    gc.collect()    # the engine's reference cycles hold its slot cache
    if res["completed"] != len(reqs) or res["failed"] or res["rejected"]:
        fail(f"{mode}: {res['completed']} of {len(reqs)} completed, "
             f"{res['failed']} failed, {res['rejected']} rejected")
    short = [r.req_id for r in reqs if len(r.output_tokens) != max_new]
    if short:
        fail(f"{mode}: requests {short} did not emit {max_new} tokens")
    return reqs, res, clock.seconds - c0, placement


def memory(tag, chips):
    stats = [c.memory_stats() or {} for c in chips]
    print(f"memory {tag}: bytes_in_use="
          f"{[m.get('bytes_in_use') for m in stats]} peak_bytes_in_use="
          f"{[m.get('peak_bytes_in_use') for m in stats]} bytes_limit="
          f"{[m.get('bytes_limit') for m in stats]}", flush=True)


def observe(tag, reqs, res, compile_s, chips):
    print(f"bring-up observation (not a benchmark result): {tag}: "
          f"compile_s={compile_s} "
          f"ttft_p50_s={float(np.median([r.ttft for r in reqs]))} "
          f"tpot_p50_s={float(np.median([r.tpot for r in reqs]))} "
          f"output_tokens_per_s={res['output_tokens_per_s']}", flush=True)
    memory(f"after {tag}", chips)


def greedy_reference(model, params, prompts):
    """Batch-1 greedy decoding with plain jitted ``model.prefill`` and
    ``model.decode``, one request at a time.  Returns the tokens and, per
    step, the gap between the two largest logits."""
    import jax
    import jax.numpy as jnp
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    seqs, gaps = [], []
    for p in prompts:
        toks = jnp.asarray(np.asarray(p, np.int32)[None, :])
        logits, cache, lens = prefill(params, {"tokens": toks},
                                      model.init_cache(1, MAX_LEN))
        out, gap = [], []
        for _ in range(MAX_NEW):
            row = np.asarray(logits[0])
            out.append(int(np.argmax(row)))
            top2 = np.partition(row, -2)[-2:]
            gap.append(float(top2[1] - top2[0]))
            if len(out) == MAX_NEW:
                break
            logits, cache = decode(params, jnp.asarray(out[-1:], jnp.int32),
                                   cache, lens)
            lens = lens + 1
        seqs.append(out)
        gaps.append(gap)
    return seqs, gaps


def one_chip(model, params, prompts, clock, chip):
    # compile every program of the burst once, so that the modes below
    # observe steady state (their compile_s shows what they still compiled)
    _, res, compile_s, _ = serve(model, params, prompts[:len(PROMPT_LENS)],
                                 "passthrough", clock, max_new=2)
    print(f"warm-up: compile_s={compile_s}", flush=True)
    memory("after warm-up", [chip])
    outputs = {}
    for mode in ONE_CHIP_MODES:
        reqs, res, compile_s, placement = serve(model, params, prompts,
                                                mode, clock)
        observe(mode, reqs, res, compile_s, [chip])
        where = placement[0]
        if not where["params_p"] == where["slot_cache"] == {chip}:
            fail(f"{mode}: weights on {where['params_p']}, slot cache on "
                 f"{where['slot_cache']}, expected {chip}")
        outputs[mode] = [r.output_tokens for r in reqs]
    base = outputs[ONE_CHIP_MODES[0]]
    for mode in ONE_CHIP_MODES[1:]:
        if outputs[mode] != base:
            fail(f"{mode} emitted other tokens than {ONE_CHIP_MODES[0]}")
    print("tokens identical across " + ", ".join(ONE_CHIP_MODES), flush=True)

    t0 = time.monotonic()
    ref, gaps = greedy_reference(model, params, prompts)
    agree = sum(a == b for out, r in zip(base, ref) for a, b in zip(out, r))
    print(f"batch-1 greedy reference ({time.monotonic() - t0:.1f} s): "
          f"{agree} of {N_REQUESTS * MAX_NEW} tokens agree "
          f"(share {agree / (N_REQUESTS * MAX_NEW)})", flush=True)
    # where a sequence first leaves the reference, and how close the
    # reference's own top-2 logits were there (a near tie flips under the
    # rounding of another batch size)
    first = [next((i for i, (a, b) in enumerate(zip(out, r)) if a != b),
                  None) for out, r in zip(base, ref)]
    print(f"first divergent step per request {first}; reference top-2 "
          f"logit gap there "
          f"{[None if i is None else g[i] for i, g in zip(first, gaps)]}; "
          f"median gap over all steps {float(np.median(gaps))}", flush=True)
    memory("after the reference", [chip])
    wrong = [i for i, (out, r) in enumerate(zip(base, ref)) if out[0] != r[0]]
    if wrong:
        fail(f"first tokens of requests {wrong} differ from batch-1 prefill")
    print("first tokens equal batch-1 model.prefill", flush=True)


def four_chips(model, params, prompts, clock, chips):
    reqs, res, compile_s, _ = serve(model, params, prompts, "passthrough",
                                    clock)
    observe("passthrough (1 chip)", reqs, res, compile_s, chips)
    base = [r.output_tokens for r in reqs]
    for tag, mode, kw in (("dynamic_pd replicas=4", "dynamic_pd",
                           {"replicas": 4}),
                          ("disagg replicas=2", "disagg", {"replicas": 2})):
        reqs, res, compile_s, placement = serve(model, params, prompts, mode,
                                                clock, **kw)
        observe(tag, reqs, res, compile_s, chips)
        if [r.output_tokens for r in reqs] != base:
            fail(f"{tag} emitted other tokens than one-chip passthrough")
        used = [c for w in placement for c in (w["chip_p"], w["chip_d"])]
        if len(set(used)) != 4:
            fail(f"{tag}: replicas use chips {used}, not 4 distinct chips")
        for w in placement:
            if not (w["params_p"] == {w["chip_p"]}
                    and w["params_d"] == w["slot_cache"] == {w["chip_d"]}):
                fail(f"{tag}: arrays off their replica's chips: {w}")
        print(f"{tag}: tokens identical to one-chip passthrough; chips "
              + " ".join(f"p={w['chip_p'].id}/d={w['chip_d'].id}"
                         for w in placement)
              + "; requests per replica "
              + str(sorted(Counter(r.instance for r in reqs).values())),
              flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip phase (replicas=4 and disagg "
                         "pairs against one-chip passthrough) only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax
    chips = jax.devices()
    chip = chips[0]
    if chip.platform != "tpu":
        fail(f"JAX finds no TPU (platform {chip.platform!r})")
    need = 4 if args.four_chips else 1
    if len(chips) < need:
        fail(f"needs {need} TPU chips, JAX finds {len(chips)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.distributed.sharding import unbox
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model

    cache_dir = use_compile_cache()
    clock = CompileClock(jax)
    print(f"device: platform={chip.platform} kind={chip.device_kind} "
          f"count={len(chips)}; compile cache {cache_dir}", flush=True)
    cfg = get_config(ARCH)
    model = build_model(cfg)
    t0 = time.monotonic()
    params = jax.block_until_ready(
        jax.jit(lambda k: unbox(model.init(k)))(jax.random.PRNGKey(args.seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    memory("after weights", chips[:need])
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}x{cfg.head_dim} kv_heads={cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={n_params} "
          f"(init {time.monotonic() - t0:.1f} s)", flush=True)
    prompts = make_prompts(cfg.vocab_size, args.seed)
    if args.four_chips:
        four_chips(model, params, prompts, clock, chips[:4])
    else:
        one_chip(model, params, prompts, clock, chip)
    print(json.dumps({"ok": True, "device": {
        "platform": chip.platform, "kind": chip.device_kind,
        "count": len(chips)}}))


if __name__ == "__main__":
    main()

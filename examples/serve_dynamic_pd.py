"""FlexNPU serving demo (real execution): the same engine code under
(a) native passthrough, (b) static PD co-location (head-of-line blocking),
(c) FlexNPU dynamic PD co-location, (d) static PD disaggregation with the
KV cache streamed across a 2-device session in layer-wise chunks —
reproducing Table 1 and Table 4's mechanisms live.  The engine
speaks only the session API (repro.core.connect); swapping modes swaps the
session backend, never the engine code — that is the transparency
property, and the outputs stay bit-identical across every mode.

Control-plane v3: ``--policy`` picks the dispatch policy by registry name
(repro.sched.make_policy); ``--kv-chunk-layers`` sets the disagg KV
transport chunking (0 = one blob per request).

    PYTHONPATH=src python examples/serve_dynamic_pd.py
        [--policy dynamic_pd] [--kv-chunk-layers 4] [--reduced]

Without ``--reduced`` it serves olmo-1b at its published config, which
needs an accelerator; ``--reduced`` serves the CPU-sized toy.
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import unbox
from repro.models import build_model
from repro.serving.engine import RealEngine
from repro.serving.request import Request

MODES = ("passthrough", "static_colocate", "dynamic_pd", "disagg")


def mk_requests(cfg, n=6, prompt=8, out=24):
    return [Request(prompt_len=prompt, max_new_tokens=out,
                    prompt_tokens=np.random.default_rng(s).integers(
                        0, cfg.vocab_size, prompt).tolist(),
                    arrival_time=0.0)
            for s in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="",
                    help="dispatch-policy registry name for the dynamic_pd "
                         "mode (fifo, static_slice, dynamic_pd)")
    ap.add_argument("--kv-chunk-layers", type=int, default=4,
                    help="disagg mode: stream the KV cache as this many "
                         "layer-group chunks (0 = one blob)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve olmo-1b's reduced-width toy (CPU runs)")
    args = ap.parse_args()

    cfg = get_config("olmo-1b")
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    print("burst of 6 requests, 2 decode slots (backlog scenario):\n")
    outputs = {}
    for mode in MODES:
        kwargs = {}
        if mode == "dynamic_pd" and args.policy:
            kwargs["policy"] = args.policy
        if mode == "disagg":
            kwargs["kv_chunk_layers"] = args.kv_chunk_layers
        eng = RealEngine(model, params, mode=mode, max_num_seqs=2,
                         max_len=64, **kwargs)
        reqs = mk_requests(cfg)
        try:
            res = eng.run(reqs, timeout=300)
        finally:
            eng.shutdown()
        outputs[mode] = [r.output_tokens for r in reqs]
        assert eng.session.stats()[0]["streams"] == 0, \
            "engine shutdown must release its stream handles"
        note = (f"  (KV x{args.kv_chunk_layers} chunks)"
                if mode == "disagg" and args.kv_chunk_layers else "")
        print(f"{mode:18s} tok/s={res['output_tokens_per_s']:7.1f}  "
              f"TTFT mean={res['ttft_mean_s'] * 1e3:8.1f}ms  "
              f"p99={res['ttft_p99_s'] * 1e3:8.1f}ms  "
              f"TPOT={res['tpot_mean_s'] * 1e3:6.1f}ms{note}")
    same = all(outputs[m] == outputs["passthrough"] for m in MODES)
    print(f"\noutputs bit-identical across all scheduling modes: {same}")
    print("(transparency: scheduling and KV transport change WHEN work "
          "runs and WHERE bytes live, never WHAT it computes)")


if __name__ == "__main__":
    main()

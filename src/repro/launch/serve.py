"""Serving launcher: continuous-batching engine with the paged BlockList
PagedAttention (the paper's technique) — ``python -m repro.launch.serve
--arch smollm-360m --requests 8 --reduced``.

``--trace path.json`` replays a recorded/synthetic trace (repro.perf) in
deterministic virtual time instead of the synthetic workload and reports the
SLO scorecard; ``--policy auto`` resolves the whole policy triple from the
committed perf table for the trace's scenario (docs/perf_gate.md)."""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.config import ServeConfig, get_config
from repro.launch import runtime
from repro.models.api import build_model
from repro.serving.engine import Request, ServingEngine


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--backend", default="auto",
                   help="operator-backend preference for the paged-attention "
                        "hot path (auto | ref | xla | pallas | "
                        "pallas_interpret); resolved through "
                        "repro.core.dispatch and reported in metrics")
    from repro.serving import policy as policy_lib
    for axis in policy_lib.AXES:
        p.add_argument(f"--{axis}", default=policy_lib.DEFAULTS[axis],
                       choices=policy_lib.names(axis),
                       help=f"serving {axis} policy (repro.serving.policy); "
                            "resolved through the policy registry and "
                            "reported in metrics")
    p.add_argument("--policy", default="",
                   help="convenience triple: one name for all three axes "
                        "(e.g. 'auto') or 'admission/preemption/eviction'; "
                        "overrides the per-axis flags")
    p.add_argument("--trace", default="",
                   help="path to a repro.perf.trace JSON to replay in "
                        "deterministic virtual time instead of the synthetic "
                        "workload (docs/perf_gate.md)")
    p.add_argument("--slo-ttft", type=float, default=1.0,
                   help="p99 TTFT target in virtual seconds for --trace "
                        "scoring")
    p.add_argument("--slo-tpot", type=float, default=0.3,
                   help="p99 TPOT target in virtual seconds for --trace "
                        "scoring")
    from repro.serving import spec as spec_lib
    p.add_argument("--spec", default=spec_lib.OFF,
                   choices=spec_lib.names() + sorted(spec_lib.ALIASES),
                   help="speculative-decoding proposer (repro.serving.spec); "
                        "'off' decodes one token per request per step")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens proposed+verified per request "
                        "per step")
    p.add_argument("--devices", type=int, default=0,
                   help="model-axis device count of the serving mesh "
                        "(docs/sharded_serving.md); 0/1 = single-device "
                        "engine, > 1 builds a mesh via repro.launch.mesh "
                        "and runs the sharded fused step (greedy streams "
                        "stay bit-identical)")
    p.add_argument("--overlap", default="off", choices=("on", "off"),
                   help="async overlapped engine loop "
                        "(docs/async_engine.md): step N+1's host work runs "
                        "while step N is on device; greedy streams stay "
                        "bit-identical")
    p.add_argument("--prefetch-depth", type=int, default=0,
                   help="KV-page DMA ring depth for the Pallas chunked "
                        "kernel (0/1 = BlockSpec pipeline, >= 2 = "
                        "multi-buffered manual DMA; ignored by jnp backends)")
    p.add_argument("--q-chunk", type=int, default=16,
                   help="query-tile rows of the chunked paged-attention "
                        "kernel grid (the op family's q_chunk tunable; "
                        "ignored by jnp backends)")
    p.add_argument("--attn-impl", default="ragged",
                   choices=("ragged", "chunked"),
                   help="attention op family for the fused step "
                        "(docs/ragged_kernel.md): 'ragged' = ONE launch for "
                        "prefill + decode over the fused KV pool, 'chunked' "
                        "= the token-lane path on split views; greedy "
                        "streams are bit-identical")
    p.add_argument("--num-queries-per-block", type=int, default=0,
                   help="ragged-kernel query-tile rows (0 = consult the "
                        "committed autotune table BENCH_010.json, falling "
                        "back to the registry default)")
    p.add_argument("--num-kv-pages-per-block", type=int, default=0,
                   help="fused KV pages per ragged grid step — the "
                        "double-buffered DMA ring holds 2x this many pages "
                        "in VMEM (0 = autotune table, then registry default)")
    p.add_argument("--vmem-limit-bytes", type=int, default=0,
                   help="VMEM cap for the ragged kernel's fused-page ring; "
                        "clamps the page group and is forwarded to the "
                        "Mosaic compiler (0 = autotune table / uncapped)")
    p.add_argument("--sanitize", default="off", choices=("on", "off"),
                   help="runtime sanitizers (docs/static_analysis.md): "
                        "retrace guard, host-sync guard around the overlap "
                        "build half, allocator invariant checks after every "
                        "step; counters land in metrics as sanitize.*")
    p.add_argument("--roles", default="",
                   help="'' = monolithic engine; 'prefill,decode' (or "
                        "'split') = disaggregated two-role serving "
                        "(docs/disaggregated.md): prompts prefill on one "
                        "engine, KV blocks hand off through the allocator, "
                        "decode runs on the other; greedy streams stay "
                        "bit-identical")
    p.add_argument("--host-blocks", type=int, default=0,
                   help="host-memory KV tier capacity in blocks (0 = "
                        "HBM-only): evicted cached-free blocks demote to a "
                        "host LRU and promote back on prefix hit — pair "
                        "with --eviction tiered (docs/disaggregated.md)")
    args = p.parse_args()
    if args.policy:
        parts = args.policy.split("/")
        if len(parts) == 1:
            parts = parts * 3
        if len(parts) != 3:
            p.error("--policy takes one name or admission/preemption/eviction")
        args.admission, args.preemption, args.eviction = parts

    runtime.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(dtype="float32")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    serve = ServeConfig(model=args.arch, kv_block_size=args.block_size,
                        max_batch=args.requests, backend=args.backend,
                        admission=args.admission, preemption=args.preemption,
                        eviction=args.eviction, spec=args.spec,
                        spec_k=args.spec_k, devices=args.devices,
                        overlap=args.overlap == "on",
                        prefetch_depth=args.prefetch_depth,
                        q_chunk=args.q_chunk,
                        attn_impl=args.attn_impl,
                        num_queries_per_block=args.num_queries_per_block,
                        num_kv_pages_per_block=args.num_kv_pages_per_block,
                        vmem_limit_bytes=args.vmem_limit_bytes,
                        sanitize=args.sanitize == "on",
                        roles=args.roles, host_blocks=args.host_blocks,
                        trace=args.trace)
    trace = None
    ctx = contextlib.nullcontext()
    if serve.trace:
        from repro.perf.table import perf_context
        from repro.perf.trace import LengthModel, Trace
        trace = Trace.load(serve.trace)
        # Full-fit pool for the demo CLI; the benchmark scenarios starve the
        # pool deliberately, the launcher shouldn't.
        total_blocks = sum(
            -(-(len(r.prompt) + r.max_new_tokens) // args.block_size) + 1
            for r in trace.requests)
        # The replay context keys the `auto` triple's perf-table lookup and
        # feeds predicted-length's cost model; engines resolve policies at
        # construction, so it must wrap the ctor.
        ctx = perf_context(scenario=trace.scenario,
                           length_model=LengthModel.fit(trace))
    else:
        total_blocks = args.requests * (
            -(-(args.prompt_len + args.max_new) // args.block_size) + 1)
    # ServeConfig.devices > 1 makes the engine build the serving mesh itself
    # (repro.launch.mesh.make_serving_mesh) and run the sharded fused step.
    # ServeConfig.roles builds the disaggregated two-role frontend instead:
    # prefill and decode engines each get the full pool (equal HBM per
    # role), pinned to separate devices when the host has two or more.
    with ctx:
        if serve.roles:
            from repro.serving.disagg import DisaggEngine
            devs = jax.devices()
            pair = (devs[0], devs[1]) if len(devs) >= 2 else None
            engine = DisaggEngine(model, params, cfg, serve,
                                  num_blocks=total_blocks, devices=pair)
        else:
            engine = ServingEngine(model, params, cfg, serve,
                                   num_blocks=total_blocks)

    t0 = time.time()
    if trace is not None:
        from repro.perf import replay as replay_lib
        result = replay_lib.replay(engine, trace)
        report = replay_lib.score(result, replay_lib.Slo(
            ttft_s=args.slo_ttft, tpot_s=args.slo_tpot))
    else:
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            engine.submit(Request(
                req_id=i,
                prompt=rng.integers(0, cfg.vocab_size, (args.prompt_len,),
                                    dtype=np.int32),
                max_new_tokens=args.max_new))
        engine.run_until_done()
    dt = time.time() - t0
    m = engine.metrics()
    if trace is not None:
        c = result.counters()
        print(f"replayed trace {trace.name} [{trace.scenario}] "
              f"{len(trace.requests)} requests in {result.steps} virtual "
              f"steps ({c['idle_ff']} idle fast-forwards)")
        print(f"virtual TTFT p50 {report.p50_ttft_s:.2f} / p99 "
              f"{report.p99_ttft_s:.2f} s  TPOT p50 {report.p50_tpot_s:.3f} "
              f"/ p99 {report.p99_tpot_s:.3f} s  attainment "
              f"ttft={report.attainment_ttft:.0%} "
              f"tpot={report.attainment_tpot:.0%}  "
              f"SLO {'MET' if report.ok else 'MISSED'} "
              f"(targets {args.slo_ttft}s / {args.slo_tpot}s)")
    print(f"served on {runtime.device_line()}")
    print(f"served {m['finished']} requests, {m['output_tokens']} tokens "
          f"in {dt:.2f}s ({m['output_tokens']/dt:.1f} tok/s) "
          f"[backend={m['backend']} devices={m['devices']} "
          f"mesh={m['mesh_shape']} overlap={m['overlap']} "
          f"prefetch_depth={m['prefetch_depth']} q_chunk={m['q_chunk']}]")
    print(f"attn {m['attn_impl']}  "
          f"num_queries_per_block={m['num_queries_per_block']}  "
          f"num_kv_pages_per_block={m['num_kv_pages_per_block']}  "
          f"vmem_limit_bytes={m['vmem_limit_bytes']}")
    print(f"TTFT p50 {m['p50_ttft_s']*1e3:.1f} / p99 {m['p99_ttft_s']*1e3:.1f} ms  "
          f"TPOT p50 {m['p50_tpot_s']*1e3:.1f} / p99 {m['p99_tpot_s']*1e3:.1f} ms")
    print(f"preemptions {m['preemptions']}  "
          f"prefix hit rate {m['prefix_hit_rate']:.2f}  "
          f"cow copies {m['cow_copies']}")
    print(f"policies {m['admission_policy']}/{m['preemption_policy']}/"
          f"{m['eviction_policy']}  counters {m['policy_counters']}")
    t = m["tier"]
    print(f"role {m['role']}  tier hbm={t['hbm_blocks']} "
          f"host={t['host_blocks']} (used {t['host_blocks_used']})  "
          f"demotes {t['demotes']}  promotes {t['promotes']}  "
          f"hits {t['hits']}  drops {t['drops']}")
    if serve.roles:
        h = m["handoff_ms"]
        print(f"handoffs {m['handoffs']}  latency p50 {h['p50']:.2f} / "
              f"p99 {h['p99']:.2f} ms  prefill steps "
              f"{m['roles']['prefill']['steps']}  decode steps "
              f"{m['roles']['decode']['steps']}")
    sz = m["sanitize"]
    if sz["enabled"]:
        print(f"sanitize on  retraces {sz['retraces']}  "
              f"host-sync trips {sz['transfer_guard_trips']}  "
              f"invariant checks {sz['invariant_checks']}  "
              f"allowed host syncs {sz['allowed_host_syncs']}")
    s = m["spec"]
    print(f"spec {s['proposer']} k={s['k']}  "
          f"accept_rate {s['acceptance_rate']:.2f}  "
          f"mean_accepted {s['mean_accepted_len']:.2f}  "
          f"tokens/step {m['tokens_per_step']:.2f}")


if __name__ == "__main__":
    main()

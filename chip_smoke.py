#!/usr/bin/env python3
"""One pass of the served path on a TPU, through the entry points users call.

    python chip_smoke.py             # one chip: phases a, b, c
    python chip_smoke.py --chips 4   # phase a, then only phase d

Serves smollm-360m at its published widths (32 layers, d_model 960, 15/5
heads, head_dim 64, vocab 49,152, bf16) with random weights made from
``--seed``:

a. device  JAX must report a TPU; on any other platform the script exits
           non-zero before any work.
b. kernel  ``paged_attention_ragged`` with ``backend="pallas"`` (explicit,
           so strict) against ``backend="ref"`` on the same chip, at the
           model's head shapes, over mixed prefill-chunk and decode lanes.
c. engine  ``ServingEngine`` from ``get_config("smollm-360m")`` serves a few
           requests to completion with the Pallas ragged kernel, leak-free,
           twice: a cold batch that compiles and a warm batch that does not;
           its first-step logits agree with a float32 dense forward.
d. mesh    (``--chips N`` only) the ``ServeConfig(devices=N)`` engine
           against the single-device engine in this process: first-step
           logits of both within a stated tolerance of each other and of a
           float32 dense forward; greedy-stream agreement reported, with
           the float32 logits at each fork.

Every phase runs in this one process: a process that has touched JAX holds
the chip, and a child that needs it would fail or hang.  Every phase raises
on failure, and the script then exits non-zero without printing a result.
The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "smollm-360m"
BLOCK_SIZE = 16
MAX_BATCH = 8
# Served requests: prompts of some hundreds of tokens, all prefilled in one
# step (they sum to less than the engine's 2,048-token step budget).
PROMPT_LENS = (384, 256, 320, 192, 448, 288)
MAX_NEW = 32
# First-step logit check: equal-length prompts, so one dense forward.
LOGIT_PROMPTS = (256, 256)
# Kernel check lanes, one (query lanes, KV length) pair per sequence:
# prefill chunks that end a prompt, a whole prompt, and decode lanes.
KERNEL_SEQS = ((48, 300), (130, 130), (17, 64), (1, 1), (1, 17), (1, 700),
               (1, 129))
# Pallas vs ref, both on bf16 inputs with float32 accumulation: they differ
# by bf16 rounding of the scores (ref), of the softmax weights and of the
# output.  max |err| / max |ref| within 2e-2 is about five bf16 ulps at the
# largest output; a page or head mixed up errs by the output's own size.
KERNEL_RTOL = 2e-2
# First-step logits against a reference: max |a - b| / max |b|.  bf16 weights
# and activations through 32 layers stay within a few percent of float32.
LOGIT_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeError(RuntimeError):
    """A phase's output is wrong."""


def check(ok, *why) -> None:
    """Raise :class:`SmokeError` unless ``ok`` (kept under ``python -O``)."""
    if not ok:
        raise SmokeError(*why)


# ------------------------------------------------------------------ phase a
def check_device(platform: str = "tpu") -> dict:
    """The devices JAX found; exits non-zero unless they are ``platform``."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != platform:
        raise SystemExit(f"chip_smoke: JAX found {dev['platform']!r} "
                         f"devices, not {platform!r}")
    return dev


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) in this process."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compiles {self.compiles} ({self.seconds:.1f} s), "
                f"persistent-cache hits {self.cache_hits}")


@functools.cache
def compile_counter() -> CompileCounter:
    """The process's one :class:`CompileCounter` (listeners stay registered)."""
    return CompileCounter()


# ------------------------------------------------------------------ phase b
def ragged_case(cfg, seed: int, seqs=KERNEL_SEQS, dtype="bfloat16"):
    """Arguments of ``paged_attention_ragged`` for ``seqs`` at ``cfg``'s
    head shapes: random q and fused pool from ``seed``, each sequence's
    pages scattered over the pool, slots permuted, padded BlockList."""
    import jax
    import jax.numpy as jnp

    a = cfg.attention
    rng = np.random.default_rng(seed)
    pages = [-(-kv // BLOCK_SIZE) for _, kv in seqs]
    nb = sum(pages) + 3                   # a few blocks nobody references
    perm = rng.permutation(nb)
    slot = rng.permutation(len(seqs))     # sequence j lives in slot[j]
    bl, br, bp, off = [], [], [], 0
    for j, n in enumerate(pages):
        bl += list(perm[off:off + n])
        br += [slot[j]] * n
        bp += list(range(n))
        off += n
    pad = 5                               # pad entries: out-of-range owner
    bl, br, bp = bl + [0] * pad, br + [len(seqs)] * pad, bp + [0] * pad
    cu_q = np.cumsum([0] + [q for q, _ in seqs])
    cu_kv = np.cumsum([0] + [kv for _, kv in seqs])
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (int(cu_q[-1]), a.num_heads, a.head_dim), dtype)
    pool = jax.random.normal(
        kp, (nb, a.num_kv_heads, BLOCK_SIZE, 2 * a.head_dim), dtype)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return (q, pool, i32(bl), i32(br), i32(bp), i32(cu_q), i32(cu_kv),
            i32(slot))


def check_kernel(cfg, backend: str = "pallas", seed: int = 0,
                 seqs=KERNEL_SEQS) -> float:
    """Max |``backend`` - ``ref``| of the ragged op on mixed lanes."""
    from repro.core.attention_api import paged_attention_ragged_op

    args = ragged_case(cfg, seed, seqs, cfg.dtype)
    out = paged_attention_ragged_op(*args, backend=backend)
    ref = paged_attention_ragged_op(*args, backend="ref")
    check(out.shape == ref.shape == args[0].shape, (out.shape, ref.shape))
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    check(np.isfinite(out).all(), "non-finite kernel output")
    err = float(np.abs(out - ref).max())
    scale = float(np.abs(ref).max())
    log(f"kernel: paged_attention_ragged {backend} vs ref, {out.shape[0]} "
        f"lanes, {len(seqs)} sequences, {int(args[2].shape[0])} BlockList "
        f"entries, heads {out.shape[1]}/{cfg.attention.num_kv_heads} "
        f"hd {out.shape[2]} {cfg.dtype}: max |err| {err:.3e}, "
        f"max |ref| {scale:.3f}, ratio {err / scale:.3e} "
        f"(tol {KERNEL_RTOL:g})")
    check(err <= KERNEL_RTOL * scale, f"kernel error {err} vs {scale}")
    return err


# ------------------------------------------------------------------ phase c
def build(cfg, seed: int = 0):
    """The model and its random weights, made on the device from ``seed``."""
    import jax

    from repro.models.api import build_model

    model = build_model(cfg, remat=False)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, params


def make_requests(cfg, prompt_lens, max_new: int, seed: int = 0,
                  first_id: int = 0):
    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(req_id=first_id + i, max_new_tokens=max_new,
                    prompt=rng.integers(0, cfg.vocab_size, (n,),
                                        dtype=np.int32))
            for i, n in enumerate(prompt_lens)]


def make_engine(model, params, cfg, *, backend: str, num_blocks: int,
                devices: int = 0):
    from repro.config import ServeConfig
    from repro.serving.engine import ServingEngine

    serve = ServeConfig(model=cfg.name, kv_block_size=BLOCK_SIZE,
                        max_batch=MAX_BATCH, backend=backend,
                        devices=devices)
    return ServingEngine(model, params, cfg, serve, num_blocks=num_blocks)


def pool_blocks(prompt_lens, max_new: int, devices: int = 1) -> int:
    """Blocks for every request at once, plus one slack block each."""
    need = sum(-(-(n + max_new) // BLOCK_SIZE) + 1 for n in prompt_lens)
    return -(-need // devices) * devices


def run_batch(eng, cfg, reqs, *, backend: str, num_blocks: int,
              label: str) -> dict:
    """Serve ``reqs`` to completion on ``eng``; check and print the batch.

    TTFT/TPOT come from this batch's requests, the per-step phase times
    (engine wall clock, ms) and the compiles from this batch's steps.
    Returns ``{req_id: output tokens}``.
    """
    counter = compile_counter()
    m0, c0 = eng.metrics(), counter.compiles
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_done()
    dt = time.perf_counter() - t0
    m = eng.metrics()
    steps = m["steps"] - m0["steps"]
    check(steps > 0, "no engine step ran")
    phases = ", ".join(
        f"{k} {(v - m0['phase_s'].get(k, 0.0)) / steps * 1e3:.2f}"
        for k, v in sorted(m["phase_s"].items()))
    tokens = m["output_tokens"] - m0["output_tokens"]
    check(all(r.ttft is not None and r.tpot is not None for r in reqs),
          "a request did not finish")
    ttft = np.array([r.ttft for r in reqs]) * 1e3
    tpot = np.array([r.tpot for r in reqs]) * 1e3
    log(f"engine[{m['backend']} devices={m['devices']}] {label}: "
        f"{len(reqs)} requests, {tokens} tokens in {dt:.2f} s wall, "
        f"{steps} steps, {counter.compiles - c0} compiles, "
        f"attn {m['attn_impl']}, TTFT p50 {np.percentile(ttft, 50):.1f} / "
        f"p99 {np.percentile(ttft, 99):.1f} ms, TPOT p50 "
        f"{np.percentile(tpot, 50):.2f} / p99 {np.percentile(tpot, 99):.2f} "
        f"ms, per step ms: {phases}, pool {m['blocks_free']}/{num_blocks} "
        f"blocks free")
    want = "sharded" if m["devices"] > 1 else backend
    max_new = reqs[0].max_new_tokens
    check(m["finished"] - m0["finished"] == len(reqs),
          (m["finished"] - m0["finished"], len(reqs)))
    check(tokens == len(reqs) * max_new, tokens)
    check(m["backend"] == want, (m["backend"], want))
    check(m["attn_impl"] == "ragged", m["attn_impl"])
    check(m["blocks_free"] == num_blocks,
          ("pool leak", m["blocks_free"], num_blocks))
    eng.alloc.check_invariants(drained=True)
    outs = {r.req_id: list(r.output) for r in reqs}
    check(all(0 <= t < cfg.vocab_size for o in outs.values() for t in o),
          "token outside the vocabulary")
    return outs


def serve(model, params, cfg, *, backend: str, prompt_lens=PROMPT_LENS,
          max_new: int = MAX_NEW, seed: int = 0, devices: int = 0):
    """Serve greedy requests to completion, two batches on one engine.

    The cold batch's first steps compile the engine's programs; the warm
    batch (fresh prompts of the same lengths, so the same shapes) runs
    them.  Returns the cold batch's ``{req_id: output tokens}``.
    """
    nb = pool_blocks(prompt_lens, max_new, max(devices, 1))
    eng = make_engine(model, params, cfg, backend=backend, num_blocks=nb,
                      devices=devices)
    check(eng.metrics()["devices"] == max(devices, 1),
          eng.metrics()["devices"])
    cold = make_requests(cfg, prompt_lens, max_new, seed)
    outs = run_batch(eng, cfg, cold, backend=backend, num_blocks=nb,
                     label="cold (compiles included)")
    warm = make_requests(cfg, prompt_lens, max_new, seed + 1000,
                         first_id=len(cold))
    run_batch(eng, cfg, warm, backend=backend, num_blocks=nb, label="warm")
    return outs


def first_step_logits(model, params, cfg, prompt_lens, seed: int, *,
                      backend: str, devices: int = 0) -> np.ndarray:
    """The logits an engine's first step computes: every prompt prefilled
    in one fused paged step, one row per request (float32, host)."""
    import jax

    nb = pool_blocks(prompt_lens, 1, max(devices, 1))
    eng = make_engine(model, params, cfg, backend=backend, num_blocks=nb,
                      devices=devices)
    reqs = make_requests(cfg, prompt_lens, 1, seed)
    for r in reqs:
        eng.submit(r)
    plan = eng.scheduler.schedule()
    check(sum(n for _, n in plan.prefill) == sum(prompt_lens),
          "the prompts do not fit one step", plan)
    lists, tokens, *_ = eng._render(plan)
    mesh = eng.mesh
    step = jax.jit(functools.partial(
        model.decode_tokens_paged,
        attn_backend=None if mesh is not None else eng.attn_backend,
        mesh=mesh, axis=eng.mesh_axis if mesh is not None else None))
    logits, _ = step(eng.params, eng.pools, lists, tokens)
    return np.asarray(logits[np.asarray([r.slot for r in reqs])], np.float32)


def dense_logits(cfg, params, prompt_lens, seed: int) -> np.ndarray:
    """Reference: the float32 dense forward's next-token logits after each
    of the prompts ``make_requests`` makes from ``seed``."""
    return dense_logits_after(
        cfg, params, [r.prompt for r in make_requests(cfg, prompt_lens, 1,
                                                      seed)])


def dense_logits_after(cfg, params, contexts) -> np.ndarray:
    """The plain dense forward in float32, highest precision: the logits
    of the token after each context, one row per context.  Contexts are
    right-padded to one length; causal attention keeps the padding out."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models.api import build_model

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    model32 = build_model(cfg32, remat=False)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    toks = np.zeros((len(contexts), max(map(len, contexts))), np.int32)
    for i, c in enumerate(contexts):
        toks[i, :len(c)] = c
    last = jnp.asarray([len(c) - 1 for c in contexts])

    @jax.jit
    def after(p, toks, last):
        logits, _ = model32.forward(p, toks)
        return logits[jnp.arange(toks.shape[0]), last]

    with jax.default_matmul_precision("highest"):
        return np.asarray(after(p32, jnp.asarray(toks), last), np.float32)


def compare_logits(name: str, got: np.ndarray, ref: np.ndarray) -> float:
    """Check ``got`` against ``ref``; returns max |got - ref| (absolute)."""
    check(got.shape == ref.shape, (got.shape, ref.shape))
    check(np.isfinite(got).all(), f"{name}: non-finite logits")
    err = float(np.abs(got - ref).max())
    rel = err / float(np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"logits: {name}: max |err| / max |ref| {rel:.3e} "
        f"(tol {LOGIT_RTOL:g}), max |err| {err:.4f}, argmax agreement "
        f"{agree:.2f}, max |ref| {float(np.abs(ref).max()):.3f}")
    check(rel <= LOGIT_RTOL, f"{name}: logit error {rel} > {LOGIT_RTOL}")
    return err


def check_engine(cfg, model, params, backend: str = "pallas", seed: int = 0,
                 prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
                 logit_prompts=LOGIT_PROMPTS):
    compare_logits(f"paged step ({backend}) vs float32 dense forward",
                   first_step_logits(model, params, cfg, logit_prompts,
                                     seed + 1, backend=backend),
                   dense_logits(cfg, params, logit_prompts, seed + 1))
    return serve(model, params, cfg, backend=backend,
                 prompt_lens=prompt_lens, max_new=max_new, seed=seed)


# ------------------------------------------------------------------ phase d
def check_mesh(cfg, model, params, chips: int, backend: str = "pallas",
               seed: int = 0, prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
               logit_prompts=LOGIT_PROMPTS) -> float:
    """The ``devices=chips`` engine against the single-device engine.

    Both first steps are checked against the float32 dense forward too, and
    where a greedy stream parts, the float32 logits at the fork say how far
    apart the two engines' choices were.
    """
    ref = dense_logits(cfg, params, logit_prompts, seed + 1)
    one = first_step_logits(model, params, cfg, logit_prompts, seed + 1,
                            backend=backend)
    many = first_step_logits(model, params, cfg, logit_prompts, seed + 1,
                             backend=backend, devices=chips)
    compare_logits(f"1-device {backend} step vs float32 dense forward",
                   one, ref)
    compare_logits(f"{chips}-device mesh step vs float32 dense forward",
                   many, ref)
    err = compare_logits(f"{chips}-device mesh step vs 1-device {backend} "
                         "step", many, one)
    one = serve(model, params, cfg, backend=backend,
                prompt_lens=prompt_lens, max_new=max_new, seed=seed)
    many = serve(model, params, cfg, backend=backend,
                 prompt_lens=prompt_lens, max_new=max_new, seed=seed,
                 devices=chips)
    prefix, forks = [], []
    for r in make_requests(cfg, prompt_lens, max_new, seed):
        a, b = one[r.req_id], many[r.req_id]
        k = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        prefix.append(k / len(a))
        if k < len(a):
            forks.append((r.req_id, k, a[k], b[k],
                          np.concatenate([r.prompt, a[:k]])))
    agree = float(np.mean(prefix))
    log(f"greedy streams: {len(one) - len(forks)}/{len(one)} identical "
        f"across {chips} devices vs 1; mean agreeing prefix {agree:.2f} of "
        f"{max_new} tokens")
    if forks:
        ref = dense_logits_after(cfg, params, [f[-1] for f in forks])
        near = 0
        for (rid, k, ta, tb, _), row in zip(forks, ref):
            top2 = np.sort(row)[-2:]
            gap = abs(float(row[ta] - row[tb]))
            near += gap <= err
            log(f"fork: request {rid} at output token {k}: 1 device chose "
                f"{ta}, {chips} devices {tb}; float32 logits there differ "
                f"by {gap:.4f} (top-2 gap {float(top2[1] - top2[0]):.4f}, "
                f"float32 argmax {int(row.argmax())})")
        log(f"forks: {near}/{len(forks)} where the float32 logits of the two "
            f"choices differ by no more than the first step's mesh-vs-one "
            f"max |err| {err:.4f}")
    return agree


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1,
                   help="1: device, kernel and engine phases; N > 1: the "
                        "N-device mesh engine against one device, only")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    # Unless told otherwise, the TPU library writes its logs under the
    # system's temporary directory when it loads.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from repro.config import get_config
    from repro.launch import runtime

    dev = check_device("tpu")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{dev['count']} device(s)")
    log(f"compile cache: {runtime.enable_compile_cache()}")
    counter = compile_counter()
    cfg = get_config(ARCH)
    a = cfg.attention
    log(f"model: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={a.num_heads}/{a.num_kv_heads} head_dim={a.head_dim} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype} seed={args.seed}")
    if args.chips == 1:
        check_kernel(cfg, "pallas", args.seed)
    t0 = time.perf_counter()
    model, params = build(cfg, args.seed)
    log(f"weights: random from seed {args.seed}, built in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.chips == 1:
        check_engine(cfg, model, params, "pallas", args.seed)
    else:
        check_mesh(cfg, model, params, args.chips, "pallas", args.seed)
    log(counter.line())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

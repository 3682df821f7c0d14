"""The system under test for decoder-only transformer configurations.

Everything the harness needs from the program goes through here: the
program's model configuration built from the configuration file, weights
made on the device from the seed in the program's parameter layout, the
serving engine, and the warm-up of its step programs.  The plain reference
(``bench/references/``) imports none of this; it reads the same weights
through :func:`reference_weights`, which only renames them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def program_config(c: dict):
    """The program's ``ModelConfig`` with every size of the configuration
    file ``c`` (Hugging Face ``config.json`` naming) written into it."""
    from repro.config import get_config

    base = get_config(c["bench"]["program_arch"])
    heads = int(c["num_attention_heads"])
    attn = dataclasses.replace(
        base.attention, num_heads=heads,
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
        qkv_bias=bool(c.get("attention_bias", c["model_type"] == "qwen2")),
        qk_norm=False, rope_theta=float(c["rope_theta"]))
    return dataclasses.replace(
        base, num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]), attention=attn,
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        act=str(c["hidden_act"]), dtype=str(c["torch_dtype"]))


def build_model(cfg):
    from repro.models.api import build_model as build

    return build(cfg, remat=False)


def _leaf_scale(path: str, shape) -> tuple:
    """(mean, std) of a weight by its role: norm scales near 1, biases
    small, matrices at 1/sqrt(fan-in)."""
    if path.endswith("scale"):
        return 1.0, 0.1
    if path.split("/")[-1] in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if path.endswith("table"):
        return 0.0, float(shape[-1]) ** -0.5
    return 0.0, float(shape[-2]) ** -0.5


def make_weights(model, seed: int):
    """Random weights for every leaf of the program's parameter tree, made
    on the device from ``seed`` in one jitted call, in the served dtype.

    Unlike the program's own ``init``, norm scales and the QKV biases get
    random values too, so the comparison with the reference covers them.
    """
    abstract = model.init_abstract()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in leaves]

    def make(key):
        out = []
        for i, ((_, a), path) in enumerate(zip(leaves, paths)):
            mean, std = _leaf_scale(path, a.shape)
            x = jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                  jnp.float32)
            out.append((mean + std * x).astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(key_seed(seed)))


def key_seed(seed: int) -> int:
    """A 31-bit PRNG seed drawn from any whole-number ``seed``."""
    return int(np.random.default_rng([seed, 1]).integers(2 ** 31))


def reference_weights(params) -> dict:
    """The weights under the reference's names (the same arrays)."""
    layers, attn = params["layers"], params["layers"]["attn"]
    out = {"embed": params["embed"]["table"],
           "final_norm": params["final_norm"]["scale"],
           "layers": {"ln1": layers["ln1"]["scale"],
                      "ln2": layers["ln2"]["scale"],
                      "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"],
                      "wo": attn["wo"],
                      "w_gate": layers["mlp"]["w_gate"],
                      "w_up": layers["mlp"]["w_up"],
                      "w_down": layers["mlp"]["w_down"]}}
    for b in ("bq", "bk", "bv"):
        if b in attn:
            out["layers"][b] = attn[b]
    if "head" in params:
        out["head"] = params["head"]["table"]
    return out


def request_types():
    """The program's request class and its state enum."""
    from repro.serving.request import Request, RequestState

    return Request, RequestState


def build_engine(model, params, cfg, serve: dict, num_blocks: int,
                 backend: str):
    """A ``ServingEngine`` as the configuration file's ``serve`` group
    states it, with a pool of ``num_blocks`` blocks."""
    from repro.config import ServeConfig
    from repro.serving.engine import ServingEngine

    sc = ServeConfig(model=cfg.name, kv_block_size=int(serve["block_size"]),
                     max_batch=int(serve["max_batch"]),
                     prefill_chunk=int(serve["prefill_chunk"]),
                     backend=backend, attn_impl=str(serve["attn_impl"]))
    return ServingEngine(model, params, cfg, sc, num_blocks=num_blocks)


def buckets(min_running: int, max_running: int, max_batch: int,
            prefill_chunk: int) -> list:
    """The (token lanes, slots) shapes a step can take while between
    ``min_running`` and ``max_running`` requests are admitted.

    The engine rounds lanes and slots up to powers of two (at least 8),
    keeps slots dense (so slots = bucket(running), capped at max_batch),
    and gives every admitted request at least one lane unless the prefill
    budget is spent; so lanes >= slots, and lanes <= running + budget.
    """
    def pow2(n):
        b = 8
        while b < n:
            b *= 2
        return b

    out = []
    for n in range(min_running, max_running + 1):
        bs = min(pow2(n), max_batch)
        t = pow2(n)
        while t <= pow2(n + prefill_chunk):
            if (t, bs) not in out:
                out.append((t, bs))
            t *= 2
    return sorted(out)


def warm(engine, shapes) -> None:
    """Compile the engine's step for each (lanes, slots) shape, without
    running it: the step's jitted function is lowered and compiled with
    arguments of the shapes ``ServingEngine._render`` gives it, so the
    first real call of each shape finds it compiled.  (The program has no
    public way to do this; the engine's private step is named here and
    nowhere else.)
    """
    cap = engine.max_total
    i32 = np.int32
    for T, Bs in shapes:
        lists = {
            "block_list": np.zeros((cap,), i32),
            "block_req": np.full((cap,), Bs, i32),
            "block_pos": np.zeros((cap,), i32),
            "kv_lens": np.zeros((Bs,), i32),
            "token_req": np.full((T,), Bs, i32),
            "token_pos": np.zeros((T,), i32),
            "cu_q_lens": np.zeros((Bs + 1,), i32),
            "cu_kv_lens": np.zeros((Bs + 1,), i32),
            "seq_slot": np.full((Bs,), Bs, i32),
            "slots": np.tile(np.array([cap, 0], i32), (T, 1)),
            "last_lane": np.zeros((Bs,), i32),
        }
        lists = {k: jnp.asarray(v) for k, v in lists.items()}
        args = (engine.params, engine.pools, lists,
                jnp.zeros((T,), jnp.int32), jnp.full((T,), -1, jnp.int32),
                engine._dummy_prev, jax.random.fold_in(engine._key, 0),
                jnp.zeros((Bs,), jnp.float32), jnp.zeros((Bs,), jnp.int32),
                jnp.ones((Bs,), jnp.float32))
        engine._step_fn.lower(*args).compile()

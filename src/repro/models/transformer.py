"""Decoder-only transformer LM: dense + MoE + VLM variants, scan-over-layers.

Covers qwen3-moe-235b, granite-moe-1b, qwen2-1.5b, qwen3-32b, internlm2-20b,
smollm-360m, internvl2-26b (ViT-stub), llama31-8b/70b.

Functional API:
  init(key)                                -> params
  forward(params, tokens, extra_embeds)    -> logits (train / prefill)
  forward_with_kv(...)                     -> (logits, (k, v) stacked (L,...))
  init_decode_cache(batch, max_seq)        -> contiguous cache pytree
  decode_step(params, cache, tokens)       -> (logits, cache)       [pjit path]
  decode_step_paged(params, pools, lists…) -> (logits, pools)       [paper path]
  decode_tokens_paged(params, pools, …)    -> (logits, pools)  [chunked prefill
                                               + decode fused in one program]
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import attention_api, paged_kv
from repro.distributed.act_sharding import constrain_batch
from repro.training import remat as remat_lib
from repro.layers import attention as attn_lib
from repro.layers import moe as moe_lib
from repro.layers.embedding import embed, embedding_init, head_init, unembed
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.norm import rmsnorm, rmsnorm_init
from repro.layers.rope import apply_rope

NEG_INF = -1e30


class TransformerLM:
    def __init__(self, cfg: ModelConfig, *, q_chunk: int = 512,
                 shard_moe: bool = False, remat: bool = True,
                 scan_layers: bool = True, unroll_attn: bool = False,
                 moe_groups: int = 1):
        self.cfg = cfg
        self.q_chunk = q_chunk
        self.shard_moe = shard_moe
        self.remat = remat
        self.scan_layers = scan_layers
        self.unroll_attn = unroll_attn
        self.moe_groups = moe_groups
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------ init
    def _layer_init(self, key):
        cfg = self.cfg
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {
            "ln1": rmsnorm_init(cfg.d_model, self.dtype),
            "ln2": rmsnorm_init(cfg.d_model, self.dtype),
            "attn": attn_lib.attention_init(k1, cfg.d_model, cfg.attention,
                                            self.dtype),
        }
        if cfg.moe is not None:
            p["moe"] = moe_lib.moe_init(k2, cfg.d_model, cfg.moe, self.dtype)
        else:
            p["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.act, self.dtype)
        return p

    def init(self, key):
        cfg = self.cfg
        ke, kl, kh = jax.random.split(key, 3)
        layer_keys = jax.random.split(kl, cfg.num_layers)
        params = {
            "embed": embedding_init(ke, cfg.vocab_size, cfg.d_model, self.dtype),
            "layers": jax.vmap(self._layer_init)(layer_keys),
            "final_norm": rmsnorm_init(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            params["head"] = head_init(kh, cfg.vocab_size, cfg.d_model, self.dtype)
        return params

    def init_abstract(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # --------------------------------------------------------------- forward
    def _block(self, lp, x, positions, *, collect_kv: bool):
        cfg = self.cfg
        x = constrain_batch(x)
        h, kv = attn_lib.attention_block(
            lp["attn"], rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
            cfg.attention, chunk=self.q_chunk, unroll=self.unroll_attn)
        x = x + h
        if cfg.moe is not None:
            h, aux = moe_lib.moe_apply(
                lp["moe"], rmsnorm(lp["ln2"], x, cfg.norm_eps), cfg.moe,
                shard=self.shard_moe, groups=self.moe_groups)
        else:
            h = mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps), cfg.act)
            aux = jnp.zeros((), jnp.float32)
        return x + h, aux, kv

    def _embed_inputs(self, params, tokens, extra_embeds):
        x = embed(params["embed"], tokens)
        if extra_embeds is not None:  # VLM: prepend vision-stub embeddings
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        return x

    def forward(self, params, tokens, extra_embeds=None, *,
                return_kv: bool = False, last_only: bool = False):
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, extra_embeds)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(carry, lp):
            x, aux_sum = carry
            x, aux, kv = self._block(lp, x, positions, collect_kv=return_kv)
            return (x, aux_sum + aux), (kv if return_kv else None)

        if self.scan_layers:
            body_fn = remat_lib.wrap(body, self.remat)
            (x, aux), kvs = jax.lax.scan(
                body_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])
        else:  # unrolled (cost probes / scan-vs-unroll experiments)
            body_fn = remat_lib.wrap(body, self.remat)
            carry = (x, jnp.zeros((), jnp.float32))
            kv_list = []
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda t: t[i], params["layers"])
                carry, kv = body_fn(carry, lp)
                if return_kv:
                    kv_list.append(kv)
            x, aux = carry
            kvs = (jax.tree.map(lambda *xs: jnp.stack(xs), *kv_list)
                   if return_kv else None)
        if last_only:
            x = x[:, -1:]
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params.get("head", params["embed"]), x)
        if return_kv:
            return logits, aux, kvs
        return logits, aux

    # ---------------------------------------------------------------- decode
    def init_decode_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        a = cfg.attention
        shape = (cfg.num_layers, batch, max_seq, a.num_kv_heads, a.head_dim)
        return {
            "k": jnp.zeros(shape, self.dtype),
            "v": jnp.zeros(shape, self.dtype),
            "seq_lens": jnp.zeros((batch,), jnp.int32),
        }

    def _decode_attn(self, lp, x, k_cache, v_cache, seq_lens):
        """One decode token against a contiguous cache.

        §Perf A2 (revised): GSPMD splits the softmax over the model-sharded
        seq dim into local partials + tiny stat all-reduces on its own, so
        the dense form IS flash-decoding at the collective level; an
        explicit KV-chunk scan (tried first) broke the seq sharding and
        all-gathered every chunk. Scores use ``preferred_element_type`` so
        no f32 copies of q/k/cache are materialized.
        """
        cfg = self.cfg
        a = cfg.attention
        B = x.shape[0]
        q, k_new, v_new = attn_lib.project_qkv(
            lp["attn"], x[:, None], a, seq_lens[:, None])
        q = q[:, 0]                                       # (B,H,hd)
        # append new kv at position seq_lens
        k_cache = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n, i, axis=0))(k_cache, k_new, seq_lens)
        v_cache = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n, i, axis=0))(v_cache, v_new, seq_lens)
        S = k_cache.shape[1]
        KV = a.num_kv_heads
        qg = q.reshape(B, KV, a.num_heads // KV, a.head_dim)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                            preferred_element_type=jnp.float32)
        scores = scores * a.head_dim ** -0.5
        mask = jnp.arange(S)[None] <= seq_lens[:, None]   # includes new token
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
        ctx = jnp.einsum("bkgs,bskd->bkgd", w, v_cache)
        return ctx.reshape(B, -1), k_cache, v_cache

    def decode_step(self, params, cache, tokens):
        """tokens (B,) -> logits (B, V); contiguous cache (pjit path)."""
        cfg = self.cfg
        seq_lens = cache["seq_lens"]
        x = embed(params["embed"], tokens)                # (B, D)

        def body(x, inp):
            lp, k_c, v_c = inp
            x = constrain_batch(x)
            h = rmsnorm(lp["ln1"], x[:, None], cfg.norm_eps)[:, 0]
            ctx, k_c, v_c = self._decode_attn(lp, h, k_c, v_c, seq_lens)
            x = x + jnp.einsum("be,ed->bd", ctx, lp["attn"]["wo"])
            h = rmsnorm(lp["ln2"], x[:, None], cfg.norm_eps)
            if cfg.moe is not None:
                o, _ = moe_lib.moe_apply(lp["moe"], h, cfg.moe,
                                         shard=self.shard_moe,
                                         full_capacity=True,
                                         groups=self.moe_groups)
            else:
                o = mlp_apply(lp["mlp"], h, cfg.act)
            return x + o[:, 0], (k_c, v_c)

        if self.scan_layers:
            x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                               cache["v"]))
        else:
            ks, vs = [], []
            for i in range(cfg.num_layers):
                inp = jax.tree.map(lambda t: t[i],
                                   (params["layers"], cache["k"], cache["v"]))
                x, (k_i, v_i) = body(x, inp)
                ks.append(k_i)
                vs.append(v_i)
            k, v = jnp.stack(ks), jnp.stack(vs)
        x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)
        logits = unembed(params.get("head", params["embed"]), x)[:, 0]
        new_cache = {"k": k, "v": v, "seq_lens": seq_lens + 1}
        return logits, new_cache

    def decode_step_paged(self, params, pools, lists, tokens, *,
                          axis: Optional[str] = None,
                          attn_backend: Optional[str] = None):
        """Paged decode (the paper's technique).

        pools: {"k","v"} (L, NB, BS, KV, HD); lists: dict with block_list /
        block_req / block_pos (flat BlockList), seq_lens (B,), slots (B,2).
        ``axis`` set ⇒ running inside shard_map with the pool sequence-sharded
        over that mesh axis (flash-decoding combine).  ``attn_backend``
        routes the attention op through the unified registry (resolved
        host-side at trace time; the sharded path is collective-combined and
        stays on its shard_map implementation).
        """
        cfg = self.cfg
        a = cfg.attention
        seq_lens = lists["seq_lens"]
        x = embed(params["embed"], tokens)

        def body(x, inp):
            lp, pk, pv = inp
            h = rmsnorm(lp["ln1"], x[:, None], cfg.norm_eps)
            q, k_new, v_new = attn_lib.project_qkv(lp["attn"], h, a,
                                                   seq_lens[:, None])
            # Non-owning ranks carry out-of-bounds slots -> scatter drops them.
            pk = paged_kv.append_to_pool(pk, k_new[:, 0], lists["slots"])
            pv = paged_kv.append_to_pool(pv, v_new[:, 0], lists["slots"])
            if axis is None:
                ctx = attention_api.paged_attention(
                    q[:, 0], pk, pv, lists["block_list"], lists["block_req"],
                    lists["block_pos"], seq_lens + 1, backend=attn_backend)
            else:
                ctx = attention_api.paged_attention_sharded(
                    q[:, 0], pk, pv, lists["block_list"], lists["block_req"],
                    lists["block_pos"], seq_lens + 1, axis=axis)
            x = x + jnp.einsum("be,ed->bd", ctx.reshape(x.shape[0], -1),
                               lp["attn"]["wo"])
            h = rmsnorm(lp["ln2"], x[:, None], cfg.norm_eps)
            if cfg.moe is not None:
                o, _ = moe_lib.moe_apply(lp["moe"], h, cfg.moe,
                                         shard=self.shard_moe,
                                         full_capacity=True,
                                         groups=self.moe_groups)
            else:
                o = mlp_apply(lp["mlp"], h, cfg.act)
            return x + o[:, 0], (pk, pv)

        x, (pk, pv) = jax.lax.scan(body, x, (params["layers"], pools["k"],
                                             pools["v"]))
        x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)
        logits = unembed(params.get("head", params["embed"]), x)[:, 0]
        return logits, {"k": pk, "v": pv}

    def _sharded_append_attend(self, mesh, axis, q, k_new, v_new, pkv,
                               lists, attn_impl="ragged"):
        """One layer's pool append + attention under shard_map (mesh path).

        ``pkv`` is the FUSED pool layer, sequence-sharded
        on its block dimension over ``axis``;
        ``block_list``/``block_req``/``block_pos`` are the (S, M) per-shard
        LOCAL BlockLists from ``BlockAllocator.build_sharded_block_lists``.
        Each rank translates the global write slots to local indices
        (non-owned lanes get an out-of-bounds sentinel the scatter drops),
        appends its lanes' fused KV to its pool shard in ONE scatter,
        computes flash partials against its local list, and the log-sum-exp
        combine (``paged_attention_ragged_sharded`` /
        ``paged_attention_chunked_sharded`` per ``attn_impl``; the ragged
        form derives its lanes from the replicated cu prefix sums) reduces
        across ``axis`` — the KV never leaves its shard.
        """
        from jax.sharding import PartitionSpec as P

        ragged = attn_impl == "ragged"

        def local(q, k_new, v_new, pkv, bl, br, bp, kv_lens, token_req,
                  token_pos, cu_q, cu_kv, seq_slot, slots):
            s = jax.lax.axis_index(axis)
            per = pkv.shape[0]                      # local blocks per shard
            blk = slots[:, 0]
            # Non-owned lanes -> index == per: out of local bounds, dropped.
            local_blk = jnp.where(blk // per == s, blk - s * per, per)
            lslots = jnp.stack([local_blk, slots[:, 1]], axis=-1)
            with jax.named_scope("kv_append"):
                pkv = paged_kv.append_to_fused_pool(pkv, k_new, v_new,
                                                    lslots)
            with jax.named_scope("attention"):
                if ragged:
                    ctx = attention_api.paged_attention_ragged_sharded(
                        q, pkv, bl[0], br[0], bp[0], cu_q, cu_kv, seq_slot,
                        axis=axis)
                else:
                    pk, pv = paged_kv.fused_kv_views(pkv)
                    ctx = attention_api.paged_attention_chunked_sharded(
                        q, pk, pv, bl[0], br[0], bp[0], kv_lens, token_req,
                        token_pos, axis=axis)
            return pkv, ctx

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P(axis),
                      P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(axis), P()), check_vma=False)
        return fn(q, k_new, v_new, pkv, lists["block_list"],
                  lists["block_req"], lists["block_pos"], lists["kv_lens"],
                  lists["token_req"], lists["token_pos"],
                  lists["cu_q_lens"], lists["cu_kv_lens"],
                  lists["seq_slot"], lists["slots"])

    def decode_tokens_paged(self, params, pools, lists, tokens, *,
                            attn_backend: Optional[str] = None,
                            q_chunk: int = 16,
                            prefetch_depth: int = 0,
                            attn_impl: str = "ragged",
                            num_queries_per_block: int = 16,
                            num_kv_pages_per_block: int = 1,
                            vmem_limit_bytes: int = 0,
                            mesh=None, axis: Optional[str] = None):
        """Fused chunked-prefill + decode over flat token lanes.

        The serving engine's single compiled program: each lane of ``tokens``
        (T,) is one token of some request — a decode token (one lane per
        decoding request) or one token of a prompt chunk (several lanes per
        prefilling request). Per layer the lane KV is appended to the FUSED
        pool (``pools["kv"]``, one scatter per layer), then
        every lane attends causally to its request's blocks through the op
        family ``attn_impl`` picks: ``"ragged"`` =
        :func:`attention_api.paged_attention_ragged_op` consuming the cu
        prefix sums in ONE launch, ``"chunked"`` = the token-lane op on
        split views of the same pool.  Greedy outputs are bit-identical
        either way (both reduce to the same flash update on the same
        values).

        lists:
          block_list/block_req/block_pos   flat BlockList keyed by slot id —
                          or, with ``mesh`` set, the (S, M) per-shard LOCAL
                          lists from ``build_sharded_block_lists``
          kv_lens   (B,)  valid KV per slot after this step's append
          token_req (T,)  owning slot of each lane (>= B ⇒ padding lane)
          token_pos (T,)  absolute position of each lane's token
          cu_q_lens (B+1,) lane-count prefix sums per committed sequence
          cu_kv_lens (B+1,) post-append KV-length prefix sums, same order
          seq_slot  (B,)  slot id per committed sequence (B ⇒ unused entry)
          slots     (T, 2) pool (block, offset) where each lane's KV lands
          last_lane (B,)  lane index holding each slot's last valid token
          logit_lanes (B, R)  [optional] lane indices to unembed per slot —
                          the speculative-verify path: each decoding slot
                          carries its last committed token plus K drafted
                          tokens, and needs a logit row per lane to judge
                          every draft in this ONE forward

        ``q_chunk``/``prefetch_depth`` tune the chunked op;
        ``num_queries_per_block``/``num_kv_pages_per_block``/
        ``vmem_limit_bytes`` tune the ragged op (autotuned — see
        docs/ragged_kernel.md; jnp backends ignore all of them).

        ``mesh``/``axis`` set ⇒ the mesh-native serving path: the pool is
        sequence-sharded on its block dimension over ``axis`` and each
        layer's append + attention runs under shard_map
        (:meth:`_sharded_append_attend`); everything outside attention is
        ordinary global-array code that GSPMD partitions against the
        TP-sharded params (``distributed.sharding.ShardingRules``).

        Returns (logits, new pools): logits (B, V) at each slot's
        ``last_lane``, or (B, R, V) at ``logit_lanes`` when present.
        """
        cfg = self.cfg
        a = cfg.attention
        token_pos = lists["token_pos"]
        if attn_impl not in ("ragged", "chunked"):
            raise ValueError(
                f"attn_impl {attn_impl!r}: expected 'ragged' or 'chunked'")
        ragged = attn_impl == "ragged"
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens)             # (T, D)

        def body(x, inp):
            lp, pkv = inp
            with jax.named_scope("qkv"):
                h = rmsnorm(lp["ln1"], x[:, None], cfg.norm_eps)
                q, k_new, v_new = attn_lib.project_qkv(lp["attn"], h, a,
                                                       token_pos[:, None])
            if mesh is not None:
                pkv, ctx = self._sharded_append_attend(
                    mesh, axis or "model", q[:, 0], k_new[:, 0],
                    v_new[:, 0], pkv, lists, attn_impl)
            else:
                # Padding lanes carry out-of-bounds slots -> scatter drops
                # them.
                with jax.named_scope("kv_append"):
                    pkv = paged_kv.append_to_fused_pool(
                        pkv, k_new[:, 0], v_new[:, 0], lists["slots"])
                with jax.named_scope("attention"):
                    if ragged:
                        ctx = attention_api.paged_attention_ragged_op(
                            q[:, 0], pkv, lists["block_list"],
                            lists["block_req"], lists["block_pos"],
                            lists["cu_q_lens"], lists["cu_kv_lens"],
                            lists["seq_slot"], backend=attn_backend,
                            num_queries_per_block=num_queries_per_block,
                            num_kv_pages_per_block=num_kv_pages_per_block,
                            vmem_limit_bytes=vmem_limit_bytes)
                    else:
                        pk, pv = paged_kv.fused_kv_views(pkv)
                        ctx = attention_api.paged_attention_chunked_op(
                            q[:, 0], pk, pv, lists["block_list"],
                            lists["block_req"], lists["block_pos"],
                            lists["kv_lens"], lists["token_req"], token_pos,
                            backend=attn_backend, q_chunk=q_chunk,
                            prefetch_depth=prefetch_depth)
            with jax.named_scope("attn_out"):
                x = x + jnp.einsum("be,ed->bd", ctx.reshape(x.shape[0], -1),
                                   lp["attn"]["wo"])
            with jax.named_scope("mlp"):
                h = rmsnorm(lp["ln2"], x[:, None], cfg.norm_eps)
                if cfg.moe is not None:
                    o, _ = moe_lib.moe_apply(lp["moe"], h, cfg.moe,
                                             shard=self.shard_moe,
                                             full_capacity=True,
                                             groups=self.moe_groups)
                else:
                    o = mlp_apply(lp["mlp"], h, cfg.act)
                x = x + o[:, 0]
            return x, pkv

        x, pkv = jax.lax.scan(body, x, (params["layers"], pools["kv"]))
        head = params.get("head", params["embed"])
        with jax.named_scope("unembed"):
            if "logit_lanes" in lists:
                # Speculative verify: a row per (slot, lane) pair, (B, R, V).
                x_sel = jnp.take(x, lists["logit_lanes"], axis=0)  # (B, R, D)
                x_sel = rmsnorm(params["final_norm"], x_sel, cfg.norm_eps)
                return unembed(head, x_sel), {"kv": pkv}
            # Unembed only each slot's last valid lane: (B, D) -> (B, V).
            x_last = jnp.take(x, lists["last_lane"], axis=0)
            x_last = rmsnorm(params["final_norm"], x_last[:, None],
                             cfg.norm_eps)
            return unembed(head, x_last)[:, 0], {"kv": pkv}

    # ---------------------------------------------------------------- loss
    def loss(self, params, batch):
        """Next-token CE. batch: tokens (B,S) [+ extra_embeds, loss_mask]."""
        logits, aux = self.forward(params, batch["tokens"],
                                   batch.get("extra_embeds"))
        V = logits.shape[-1]
        # VLM: logits include vision positions; score text positions only.
        n_extra = 0
        if batch.get("extra_embeds") is not None:
            n_extra = batch["extra_embeds"].shape[1]
            logits = logits[:, n_extra:]
        from repro.training.losses import next_token_loss
        return next_token_loss(logits, batch["tokens"],
                               batch.get("loss_mask")) + 0.01 * aux

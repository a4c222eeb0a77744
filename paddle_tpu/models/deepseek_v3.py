"""A decoder-only language model with LATENT attention and a routed-expert
layer beside shared experts — the DeepSeek-V3 block (``model_type:
"deepseek_v3"``; ``config.json`` keys as published, ``LMConfig.from_dict``
reads them): of a token the cache keeps ONE row a layer, its normalised
latent (``kv_lora_rank`` wide) and one rotary key every head shares
(``qk_rope_head_dim``), from which every head's keys and values follow by
an up-projection; the leading layers have a dense SiLU-gated feed-forward,
the others sigmoid-routed experts (a selection bias, the selected scores
normalised and scaled by ``routed_scaling_factor``) plus shared experts
that every token passes.

This file builds the SERVE STEP over the same flat batch and feeds as
``mimo_v2_flash.build_serve_step``, against ONE declared kind of cache
(``cache_specs``: every position kept, so the kind is ``global``; its
values are the leading ``kv_lora_rank`` columns of its key row, so it is
ONE pool and not a pair).  Attention runs in the ABSORBED form, for decode
rows and prefill tiles alike: the up-projection's key half is folded into
the query (``q~_h = q_nope_h W_UK[h]^T``), scores and the probabilities'
sum are taken against the latent rows in the pool, and the value half is
applied to the result (``o_h = o~_h W_UV[h]``) — the same mathematics as
expanding every cached row to per-head keys and values, without doing so.

A device may hold a share of an expert layer (``experts_held`` from
``first_expert``; the shared experts are every share's alike) and a slice
of the vocabulary.  Not built, and refused by ``config_from_dict``: a
low-rank query projection (``q_lora_rank``), group-limited routing with
more than one group, softmax scoring, rotary scaling.

Parameters, under ``prefix``: ``emb.w``; per layer ``l<i>.attn_norm.w``,
``l<i>.attn.q.w`` [d, H * (nope + rope)], ``l<i>.attn.kva.w`` [d, rank +
rope], ``l<i>.attn.kv_norm.w`` [rank], ``l<i>.attn.kvb.w`` [rank, H *
(nope + v)] (a head's columns: its keys' ``nope`` part, then its values),
``l<i>.attn.out.w`` [H * v, d], ``l<i>.ffn_norm.w``, then
``l<i>.ffn.{gate,up,down}.w`` (dense) or ``l<i>.moe.router.{w,bias}``,
``l<i>.moe.experts.{gate,up,down}.w`` (stacked over the held experts) and
``l<i>.moe.shared.{gate,up,down}.w`` (the shared experts as one
feed-forward of ``n_shared_experts`` times the expert width);
``out_norm.w``, ``head.w``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

from .. import fluid
from ..fluid import layers
from ..fluid.param_attr import ParamAttr
from .cache_spec import CacheSpec
from .step_tokens import emitted_ids, fed_tokens

__all__ = ["LMConfig", "CacheSpec", "config_from_dict", "cache_specs",
           "param_shapes", "build_serve_step", "GLOBAL"]

GLOBAL = "global"


class LMConfig(NamedTuple):
    vocab_size: int                 # rows held here (a slice, or all)
    hidden_size: int
    num_attention_heads: int
    kv_lora_rank: int               # the latent's width
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    intermediate_size: int          # dense feed-forward
    moe_intermediate_size: int
    n_routed_experts: int           # the router's width (all experts)
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    experts_held: int
    first_expert: int
    layer_moe: Tuple[bool, ...]

    @property
    def n_layer(self) -> int:
        return len(self.layer_moe)

    @property
    def d_key(self) -> int:
        """A cache row, and a query against it: latent + rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def from_dict(cls, cfg: Dict) -> "LMConfig":
        """From the published keys.  ``n_routed_experts`` counts the
        experts HELD where ``published`` states the router's width; layer
        ``i`` has experts where ``i >= first_k_dense_replace`` and ``i %
        moe_layer_freq == 0``."""
        for key, built in (("q_lora_rank", None), ("rope_scaling", None),
                           ("n_group", 1), ("topk_group", 1),
                           ("scoring_func", "sigmoid"),
                           ("norm_topk_prob", True)):
            if cfg.get(key, built) != built:
                raise NotImplementedError(
                    f"deepseek_v3: {key} = {cfg[key]!r} is not built "
                    f"(only {built!r}; see the module docstring)")
        n = int(cfg["num_hidden_layers"])
        published = cfg.get("published", {})
        held = int(cfg["n_routed_experts"])
        dense, freq = (int(cfg["first_k_dense_replace"]),
                       int(cfg.get("moe_layer_freq", 1)))
        scale = cfg.get("routed_scaling_factor")
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            intermediate_size=int(cfg["intermediate_size"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_routed_experts=int(published.get("n_routed_experts", held)),
            n_shared_experts=int(cfg.get("n_shared_experts") or 0),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            routed_scaling_factor=1.0 if scale is None else float(scale),
            experts_held=held,
            first_expert=int(cfg.get("first_expert", 0)),
            layer_moe=tuple(i >= dense and i % freq == 0 for i in range(n)))


# A decoder-only model as ``serving.paged_lm.PagedLMGenerator`` takes it
# (``models.decoder_lm`` finds this module by the published ``model_type``).
config_from_dict = LMConfig.from_dict


def cache_specs(c: LMConfig) -> Dict[str, CacheSpec]:
    """One kind: every layer keeps every position, ONE row a token (one
    KV head all query heads share) whose leading ``kv_lora_rank`` columns
    are the values."""
    return {GLOBAL: CacheSpec(GLOBAL, tuple(range(c.n_layer)),
                              c.num_attention_heads, 1, c.d_key,
                              c.kv_lora_rank, None, latent=True)}


def param_shapes(c: LMConfig, prefix: str) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every parameter of the share ``c`` describes."""
    d, h, r = c.hidden_size, c.num_attention_heads, c.kv_lora_rank
    nope, rope, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    out: Dict[str, Tuple[int, ...]] = {f"{prefix}.emb.w": (c.vocab_size, d)}
    for i, moe in enumerate(c.layer_moe):
        p = f"{prefix}.l{i}"
        out[f"{p}.attn_norm.w"] = (d,)
        out[f"{p}.attn.q.w"] = (d, h * (nope + rope))
        out[f"{p}.attn.kva.w"] = (d, r + rope)
        out[f"{p}.attn.kv_norm.w"] = (r,)
        out[f"{p}.attn.kvb.w"] = (r, h * (nope + dv))
        out[f"{p}.attn.out.w"] = (h * dv, d)
        out[f"{p}.ffn_norm.w"] = (d,)
        if moe:
            f, e = c.moe_intermediate_size, c.experts_held
            out[f"{p}.moe.router.w"] = (d, c.n_routed_experts)
            out[f"{p}.moe.router.bias"] = (c.n_routed_experts,)
            out[f"{p}.moe.experts.gate.w"] = (e, d, f)
            out[f"{p}.moe.experts.up.w"] = (e, d, f)
            out[f"{p}.moe.experts.down.w"] = (e, f, d)
            if c.n_shared_experts:
                fs = c.n_shared_experts * f
                out[f"{p}.moe.shared.gate.w"] = (d, fs)
                out[f"{p}.moe.shared.up.w"] = (d, fs)
                out[f"{p}.moe.shared.down.w"] = (fs, d)
        else:
            f = c.intermediate_size
            out[f"{p}.ffn.gate.w"] = (d, f)
            out[f"{p}.ffn.up.w"] = (d, f)
            out[f"{p}.ffn.down.w"] = (f, d)
    out[f"{prefix}.out_norm.w"] = (d,)
    out[f"{prefix}.head.w"] = (d, c.vocab_size)
    return out


def _w(name: str) -> ParamAttr:
    return ParamAttr(name=name, keep_dtype=True)


def _linear(x, size: int, name: str):
    return layers.fc(input=x, size=size, bias_attr=False, param_attr=_w(name))


def build_serve_step(c: LMConfig, *, prefix: str, pools: Dict[str, Dict],
                     n_lanes: int, n_prefill: int, prefill_slots: int,
                     chunk: int, tile: int, dtype: str = "bfloat16",
                     impl: Optional[str] = None):
    """The serve step over ``n_lanes`` decode tokens and ``n_prefill``
    chunks of ``chunk`` prompt tokens, as a program DESC: the arguments,
    feeds and results of ``mimo_v2_flash.build_serve_step`` (which
    documents them), with one kind (``global``) whose declaration
    ``pools["global"]`` names ONE pool (``k``, ``k_shape``; no ``v``) and
    no ``dec_top`` / ``pf_top`` feed (no ring).

    Every parameter is declared in the type it is resident in (matrices
    in ``dtype``; norm scales, the router's matrix and selection bias
    float32)."""
    spec = cache_specs(c)[GLOBAL]
    decl = pools[GLOBAL]
    b, s_pf = int(n_lanes), int(n_prefill) * int(chunk) // int(tile)
    t = b + int(n_prefill) * int(chunk)
    n_ids = b + int(prefill_slots)      # every variant's ids, one length
    h, d, r = c.num_attention_heads, c.hidden_size, c.kv_lora_rank
    nope, rope, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    nl = c.n_layer
    sm_scale = float(nope + rope) ** -0.5
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        block = prog.global_block()

        def feed(name, shape, dt="int32"):
            return layers.data(name, shape, dt, append_batch_size=False)

        tok, pos = fed_tokens(t, n_ids), feed("pos", [t])
        dec = {k: feed(f"dec_{k}", [b]) for k in ("len", "base")}
        pf = {k: feed(f"pf_{k}", [s_pf]) for k in ("len", "base")} \
            if s_pf else None
        pool = block.create_var(name=decl["k"], shape=decl["k_shape"],
                                dtype=decl["dtype"], persistable=True)
        pages = feed(f"{GLOBAL}_pages", [t])
        offs = feed(f"{GLOBAL}_offs", [t])
        dec_table = feed(f"dec_{GLOBAL}_table", [b, decl["table"]])
        pf_table = feed(f"pf_{GLOBAL}_table", [s_pf, decl["table"]]) \
            if s_pf else None
        out_rows = feed("out_rows", [b + int(n_prefill)])
        live = feed("live", [t]) if any(c.layer_moe) else None

        emb = layers.embedding(tok, size=[c.vocab_size, d], dtype=dtype,
                               param_attr=_w(f"{prefix}.emb.w"))
        x = layers.cast(emb, "float32")             # the residual stream
        loads = []
        for i, moe in enumerate(c.layer_moe):
            p = f"{prefix}.l{i}"
            u = layers.rms_norm(x, _w(f"{p}.attn_norm.w"), c.rms_norm_eps,
                                out_dtype=dtype)
            q_nope, q_rope = layers.split(
                layers.reshape(_linear(u, h * (nope + rope),
                                       f"{p}.attn.q.w"),
                               [t, h, nope + rope]), [nope, rope], dim=2)
            q_rope = layers.rotary_embedding(q_rope, pos, rope, c.rope_theta)
            latent, k_rope = layers.split(
                _linear(u, r + rope, f"{p}.attn.kva.w"), [r, rope], dim=1)
            latent = layers.rms_norm(latent, _w(f"{p}.attn.kv_norm.w"),
                                     c.rms_norm_eps)
            k_rope = layers.reshape(layers.rotary_embedding(
                layers.reshape(k_rope, [t, 1, rope]), pos, rope,
                c.rope_theta), [t, rope])
            # the token's row, and nothing else of it is kept; every
            # token's row first, then attention: a chunk's queries read
            # their own chunk's rows from the pool
            pool = layers.paged_row_write(
                pool, layers.concat([latent, k_rope], axis=1), pages, offs,
                i, nl)
            kvb = block.create_parameter(
                name=f"{p}.attn.kvb.w", shape=[r, h * (nope + dv)],
                dtype=dtype)
            q = layers.concat(
                [layers.latent_absorb(q_nope, kvb, "query", nope), q_rope],
                axis=2)                                     # [t, h, r+rope]
            attn = dict(layer=i, n_layer=nl, latent_values=r, impl=impl,
                        sm_scale=sm_scale, scope="attn/latent")
            parts = [q] if not s_pf else layers.split(q, [b, t - b], dim=0)
            ctx = [layers.reshape(layers.ragged_decode_attention(
                layers.reshape(parts[0], [b, 1, h, r + rope]), pool,
                dec_table, dec["len"], dec["base"], **attn), [b, h, r])]
            if s_pf:
                ctx.append(layers.reshape(layers.ragged_decode_attention(
                    layers.reshape(parts[1], [s_pf, int(tile), h, r + rope]),
                    pool, pf_table, pf["len"], pf["base"], **attn),
                    [t - b, h, r]))
            o = ctx[0] if not s_pf else layers.concat(ctx, axis=0)
            o = layers.latent_absorb(o, kvb, "output", nope)    # [t, h*dv]
            x = layers.elementwise_add(
                x, _linear(o, d, f"{p}.attn.out.w"))
            # the router reads the float32 norm output, the products
            # the model's type
            w = layers.rms_norm(x, _w(f"{p}.ffn_norm.w"), c.rms_norm_eps,
                                out_dtype="float32" if moe else dtype)
            if moe:
                y, load = layers.routed_experts(
                    w, c.n_routed_experts, c.experts_held, c.first_expert,
                    c.num_experts_per_tok, c.moe_intermediate_size,
                    f"{p}.moe", dtype=dtype, live=live, impl=impl,
                    routed_scale=c.routed_scaling_factor)
                loads.append(load)
                if c.n_shared_experts:
                    y = layers.elementwise_add(y, layers.gated_ffn(
                        w, c.n_shared_experts * c.moe_intermediate_size,
                        f"{p}.moe.shared", dtype=dtype, scope="moe/shared"))
            else:
                y = layers.gated_ffn(w, c.intermediate_size, f"{p}.ffn",
                                     dtype=dtype, scope="ffn/dense")
            x = layers.elementwise_add(x, y)
        last = layers.rms_norm(layers.gather(x, out_rows),
                               _w(f"{prefix}.out_norm.w"), c.rms_norm_eps,
                               out_dtype=dtype)
        logits = layers.vocab_logits(last, c.vocab_size,
                                     _w(f"{prefix}.head.w"))
        next_ids = emitted_ids(layers.argmax(logits, axis=-1),
                               b + int(n_prefill), n_ids)
        loads = layers.reshape(layers.concat(loads, axis=0),
                               [len(loads), c.experts_held]) \
            if loads else None
    return prog, startup, next_ids, logits, loads

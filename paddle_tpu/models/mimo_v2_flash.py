"""A decoder-only language model whose layers differ in kind: global and
sliding-window attention side by side (grouped KV heads, keys wider than
values, partial rotary embedding at two bases, a learned sink logit in the
window layers' softmax), a SiLU-gated feed-forward in the leading dense
layers and sigmoid-routed experts after them — MiMo-V2-Flash's block
(``config.json`` keys as published; ``LMConfig.from_dict`` reads them).

This file builds the SERVE STEP: one compiled dispatch over a flat batch of
tokens — one decode token per lane, then ``n_prefill`` prompt chunks of
``chunk`` tokens each — against a DECLARED CACHE SPEC per layer
(``cache_specs``): a paged split pool pair per kind of layer, with its
KV-head count and key/value widths, and ``window`` (a ring of pages) or
none.  The engine (``serving/paged_lm.py``) owns lanes, pages and feeds.

A device may hold a share of the model (expert parallelism with attention
data-parallel): ``experts_held`` experts from ``first_expert`` of every
routed layer, and a slice of the vocabulary.  The router keeps its
published width and experts per token.

Parameters, under ``prefix``: ``emb.w``; per layer ``l<i>.attn_norm.w``,
``l<i>.attn.{q,k,v,out}.w``, ``l<i>.attn.sink`` (window layers),
``l<i>.ffn_norm.w``, then ``l<i>.ffn.{gate,up,down}.w`` (dense) or
``l<i>.moe.router.{w,bias}`` and ``l<i>.moe.experts.{gate,up,down}.w``
(stacked over the held experts); ``out_norm.w``, ``head.w``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

from .. import fluid
from ..fluid import layers
from ..fluid.param_attr import ParamAttr
from .cache_spec import CacheSpec
from .step_tokens import emitted_ids, fed_tokens

__all__ = ["LMConfig", "CacheSpec", "config_from_dict", "cache_specs",
           "param_shapes", "build_serve_step", "GLOBAL", "WINDOW"]

GLOBAL, WINDOW = "global", "window"


class LMConfig(NamedTuple):
    vocab_size: int                 # rows held here (a slice, or all)
    hidden_size: int
    num_attention_heads: int
    head_dim: int                   # keys and queries
    v_head_dim: int
    num_key_value_heads: int        # global layers
    swa_num_key_value_heads: int    # window layers
    sliding_window: int
    rope_theta: float
    swa_rope_theta: float
    rotary_dim: int
    attention_value_scale: float
    layernorm_epsilon: float
    intermediate_size: int          # dense feed-forward
    moe_intermediate_size: int
    n_routed_experts: int           # the router's width (all experts)
    num_experts_per_tok: int
    experts_held: int
    first_expert: int
    layer_kinds: Tuple[str, ...]    # GLOBAL | WINDOW per layer
    layer_moe: Tuple[bool, ...]

    @property
    def n_layer(self) -> int:
        return len(self.layer_kinds)

    @classmethod
    def from_dict(cls, cfg: Dict) -> "LMConfig":
        """From the published keys.  ``n_routed_experts`` counts the
        experts HELD where ``published`` states the router's width;
        ``num_hidden_layers`` takes the patterns' first entries."""
        n = int(cfg["num_hidden_layers"])
        published = cfg.get("published", {})
        held = int(cfg["n_routed_experts"])
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            head_dim=int(cfg["head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            swa_num_key_value_heads=int(cfg["swa_num_key_value_heads"]),
            sliding_window=int(cfg["sliding_window"]),
            rope_theta=float(cfg["rope_theta"]),
            swa_rope_theta=float(cfg["swa_rope_theta"]),
            rotary_dim=int(int(cfg["head_dim"])
                           * float(cfg["partial_rotary_factor"])),
            attention_value_scale=float(cfg["attention_value_scale"]),
            layernorm_epsilon=float(cfg["layernorm_epsilon"]),
            intermediate_size=int(cfg["intermediate_size"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_routed_experts=int(published.get("n_routed_experts", held)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            experts_held=held,
            first_expert=int(cfg.get("first_expert", 0)),
            layer_kinds=tuple(WINDOW if k else GLOBAL
                              for k in cfg["hybrid_layer_pattern"][:n]),
            layer_moe=tuple(bool(m) for m in cfg["moe_layer_freq"][:n]))


# A decoder-only model as ``serving.paged_lm.PagedLMGenerator`` takes it
# (``models.decoder_lm`` finds this module by the published ``model_type``):
# ``config_from_dict``, ``cache_specs``, ``param_shapes``,
# ``build_serve_step``; of the configuration object the engine reads
# ``vocab_size`` only.
config_from_dict = LMConfig.from_dict


def cache_specs(c: LMConfig) -> Dict[str, CacheSpec]:
    out = {}
    for kind, heads, window in (
            (GLOBAL, c.num_key_value_heads, None),
            (WINDOW, c.swa_num_key_value_heads, c.sliding_window)):
        idx = tuple(i for i, k in enumerate(c.layer_kinds) if k == kind)
        if idx:
            out[kind] = CacheSpec(kind, idx, c.num_attention_heads, heads,
                                  c.head_dim, c.v_head_dim, window)
    return out


def param_shapes(c: LMConfig, prefix: str) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every parameter of the share ``c`` describes."""
    d, h = c.hidden_size, c.num_attention_heads
    out: Dict[str, Tuple[int, ...]] = {f"{prefix}.emb.w": (c.vocab_size, d)}
    for i, kind in enumerate(c.layer_kinds):
        p = f"{prefix}.l{i}"
        hkv = c.num_key_value_heads if kind == GLOBAL \
            else c.swa_num_key_value_heads
        out[f"{p}.attn_norm.w"] = (d,)
        out[f"{p}.attn.q.w"] = (d, h * c.head_dim)
        out[f"{p}.attn.k.w"] = (d, hkv * c.head_dim)
        out[f"{p}.attn.v.w"] = (d, hkv * c.v_head_dim)
        out[f"{p}.attn.out.w"] = (h * c.v_head_dim, d)
        if kind == WINDOW:
            out[f"{p}.attn.sink"] = (h,)
        out[f"{p}.ffn_norm.w"] = (d,)
        if c.layer_moe[i]:
            f, e = c.moe_intermediate_size, c.experts_held
            out[f"{p}.moe.router.w"] = (d, c.n_routed_experts)
            out[f"{p}.moe.router.bias"] = (c.n_routed_experts,)
            out[f"{p}.moe.experts.gate.w"] = (e, d, f)
            out[f"{p}.moe.experts.up.w"] = (e, d, f)
            out[f"{p}.moe.experts.down.w"] = (e, f, d)
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
    chunks of ``chunk`` prompt tokens (0: a decode-only step), as a
    program DESC.  ``pools[kind]`` declares a kind's cache: ``k`` / ``v``
    (pool variable names), ``k_shape`` / ``v_shape``, ``dtype``,
    ``table`` (the page table's width: pages of a context, or the ring).
    Prefill attention runs in tiles of ``tile`` queries, each a lane of
    the ragged kernel with its own base.

    Feeds (T = n_lanes + n_prefill * chunk; S = n_prefill * chunk / tile):
    ``tok`` [T] int64 beside ``prev_ids`` and ``tok_src`` (a row's input
    token, from the host or from the last step's ids on the device:
    ``step_tokens.fed_tokens``), ``pos`` [T] int32; per kind ``<kind>_pages`` /
    ``<kind>_offs`` [T] int32 (where each token's row goes; page 0 is the
    trash page), ``dec_<kind>_table`` [n_lanes, table], ``pf_<kind>_table``
    [S, table]; ``dec_len`` / ``dec_base`` / ``dec_top`` [n_lanes] and
    ``pf_len`` / ``pf_base`` / ``pf_top`` [S] int32 (live keys, first
    query's position, newest written window page); ``live`` [T] int32
    (nonzero: a request's token; the rows of idle lanes and of a chunk's
    padding route to no expert); ``out_rows`` [n_lanes + n_prefill] int32,
    the rows whose next token is wanted.

    Every parameter is declared in the type it is resident in (matrices
    in ``dtype``; norm scales, sinks, the router's matrix and selection
    bias float32): a loader casts to what the program declares.

    Returns ``(program, startup, next_ids, logits, loads)``: the next
    token of ``out_rows`` at the generator's one length (``n_lanes +
    prefill_slots``, zeros behind: the next step's ``prev_ids``, whichever
    variant that is), their float32 logits, and the (token, expert)
    pairs each held expert got, layer by layer ([n_moe, held] int32; None
    for a model without expert layers)."""
    specs = cache_specs(c)
    b, s_pf = int(n_lanes), int(n_prefill) * int(chunk) // int(tile)
    t = b + int(n_prefill) * int(chunk)
    n_ids = b + int(prefill_slots)      # every variant's ids, one length
    h, dk, dv, d = (c.num_attention_heads, c.head_dim, c.v_head_dim,
                    c.hidden_size)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        block = prog.global_block()

        def feed(name, shape, dt="int32"):
            return layers.data(name, shape, dt, append_batch_size=False)

        tok, pos = fed_tokens(t, n_ids), feed("pos", [t])
        dec = {k: feed(f"dec_{k}", [b]) for k in ("len", "base", "top")}
        pf = {k: feed(f"pf_{k}", [s_pf]) for k in ("len", "base", "top")} \
            if s_pf else None
        cache = {}
        for kind, spec in specs.items():
            decl = pools[kind]
            cache[kind] = {
                "k": block.create_var(name=decl["k"], shape=decl["k_shape"],
                                      dtype=decl["dtype"], persistable=True),
                "v": block.create_var(name=decl["v"], shape=decl["v_shape"],
                                      dtype=decl["dtype"], persistable=True),
                "pages": feed(f"{kind}_pages", [t]),
                "offs": feed(f"{kind}_offs", [t]),
                "dec_table": feed(f"dec_{kind}_table", [b, decl["table"]]),
                "pf_table": feed(f"pf_{kind}_table", [s_pf, decl["table"]])
                if s_pf else None}
        out_rows = feed("out_rows", [b + int(n_prefill)])
        live = feed("live", [t]) if any(c.layer_moe) else None

        emb = layers.embedding(tok, size=[c.vocab_size, d], dtype=dtype,
                               param_attr=_w(f"{prefix}.emb.w"))
        x = layers.cast(emb, "float32")             # the residual stream
        loads = []
        for i, kind in enumerate(c.layer_kinds):
            p, spec, kv = f"{prefix}.l{i}", specs[kind], cache[kind]
            li, nl = spec.layers.index(i), len(spec.layers)
            ring = spec.window is not None
            u = layers.rms_norm(x, _w(f"{p}.attn_norm.w"),
                                c.layernorm_epsilon, out_dtype=dtype)
            base = c.rope_theta if kind == GLOBAL else c.swa_rope_theta
            q = layers.rotary_embedding(
                layers.reshape(_linear(u, h * dk, f"{p}.attn.q.w"),
                               [t, h, dk]), pos, c.rotary_dim, base)
            k = layers.rotary_embedding(
                layers.reshape(_linear(u, spec.kv_heads * dk,
                                       f"{p}.attn.k.w"),
                               [t, spec.kv_heads, dk]), pos, c.rotary_dim,
                base)
            v = _linear(u, spec.kv_heads * dv, f"{p}.attn.v.w")
            # every token's row first, then attention: a chunk's queries
            # read their own chunk's keys from the pool
            kv["k"] = layers.paged_row_write(kv["k"], k, kv["pages"],
                                             kv["offs"], li, nl)
            kv["v"] = layers.paged_row_write(kv["v"], v, kv["pages"],
                                             kv["offs"], li, nl)
            sink = None
            if kind == WINDOW:
                sink = block.create_parameter(
                    name=f"{p}.attn.sink", shape=[h], dtype="float32")
            attn = dict(layer=li, n_layer=nl, v_pool=kv["v"],
                        window=spec.window, sink=sink, impl=impl,
                        out_scale=c.attention_value_scale,
                        scope=f"attn/{kind}")
            parts = [q] if not s_pf else layers.split(
                q, [b, t - b], dim=0)
            ctx = [layers.reshape(layers.ragged_decode_attention(
                layers.reshape(parts[0], [b, 1, h, dk]), kv["k"],
                kv["dec_table"], dec["len"], dec["base"],
                ring_top=dec["top"] if ring else None, **attn),
                [b, h * dv])]
            if s_pf:
                ctx.append(layers.reshape(layers.ragged_decode_attention(
                    layers.reshape(parts[1], [s_pf, int(tile), h, dk]),
                    kv["k"], kv["pf_table"], pf["len"], pf["base"],
                    ring_top=pf["top"] if ring else None, **attn),
                    [t - b, h * dv]))
            o = ctx[0] if not s_pf else layers.concat(ctx, axis=0)
            x = layers.elementwise_add(
                x, _linear(o, d, f"{p}.attn.out.w"))
            # the router reads the float32 norm output, the products
            # the model's type
            w = layers.rms_norm(
                x, _w(f"{p}.ffn_norm.w"), c.layernorm_epsilon,
                out_dtype="float32" if c.layer_moe[i] else dtype)
            if c.layer_moe[i]:
                y, load = layers.routed_experts(
                    w, c.n_routed_experts, c.experts_held, c.first_expert,
                    c.num_experts_per_tok, c.moe_intermediate_size,
                    f"{p}.moe", dtype=dtype, live=live, impl=impl)
                loads.append(load)
            else:
                f = c.intermediate_size
                y = _linear(layers.swiglu(
                    _linear(w, f, f"{p}.ffn.gate.w"),
                    _linear(w, f, f"{p}.ffn.up.w")), d, f"{p}.ffn.down.w")
            x = layers.elementwise_add(x, y)
        last = layers.rms_norm(layers.gather(x, out_rows),
                               _w(f"{prefix}.out_norm.w"),
                               c.layernorm_epsilon, out_dtype=dtype)
        logits = layers.vocab_logits(last, c.vocab_size,
                                     _w(f"{prefix}.head.w"))
        next_ids = emitted_ids(layers.argmax(logits, axis=-1),
                               b + int(n_prefill), n_ids)
        loads = layers.reshape(layers.concat(loads, axis=0),
                               [len(loads), c.experts_held]) \
            if loads else None
    return prog, startup, next_ids, logits, loads

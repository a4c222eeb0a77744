"""A decoder-only language model whose attention is GATED and QK-NORMED,
whose window layers rotate and whose global layers carry no position at
all, with four norms a layer and sigmoid-routed experts beside a shared
one — Arcee's Trinity block (``model_type: "afmoe"``; ``config.json`` keys
as published, ``LMConfig.from_dict`` reads them):

* every head of the queries and keys is RMS-normalised by itself, one
  ``[head_dim]`` scale for all heads (``q_norm``, ``k_norm``), BEFORE
  rotary; the key row goes into the cache after both;
* ``sliding_attention`` layers rotate the WHOLE head (``rope_theta``,
  halves) and see keys ``t - sliding_window < j <= t``;
  ``full_attention`` layers apply NO rotary and see every key ``j <= t``
  (the causal mask is their only notion of order);
* the heads' concatenated output is multiplied by ``sigmoid(u Wg)``, a
  projection of the attention's normed input, before the output
  projection;
* SANDWICH norms: ``attn_norm`` / ``ffn_norm`` on a sub-block's input and
  ``attn_post_norm`` / ``ffn_post_norm`` on its OUTPUT, before the
  residual add;
* the embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``);
* the first ``num_dense_layers`` layers have a dense SiLU-gated
  feed-forward, the others ``num_experts_per_tok`` of ``num_experts``
  sigmoid-routed experts (a selection bias that selects only; the selected
  scores over their sum + 1e-20, times ``route_scale``) plus
  ``num_shared_experts`` shared experts every token passes.

This file builds the SERVE STEP over the same flat batch and feeds as
``mimo_v2_flash.build_serve_step``, against TWO declared kinds of cache
(``cache_specs``: ``global`` keeps every position, ``window`` a ring of
pages), both with ``num_key_value_heads`` KV heads of ``head_dim`` keys and
values.

A device may hold a share of an expert layer (``experts_held`` from
``first_expert``; the shared expert is every share's alike) and a slice of
the vocabulary.  On a share ``ffn_post_norm`` normalises the PARTIAL
result (shared + held experts' part); in a deployment the exchange sits
before that norm.  Not built, and refused by ``config_from_dict``: rotary
scaling, group-limited routing with more than one group, softmax scoring,
unnormalised routing weights.

Parameters, under ``prefix``: ``emb.w``; per layer ``l<i>.attn_norm.w``,
``l<i>.attn.{q,k,v,gate,out}.w``, ``l<i>.attn.{q_norm,k_norm}.w``
[head_dim], ``l<i>.attn_post_norm.w``, ``l<i>.ffn_norm.w``, then
``l<i>.ffn.{gate,up,down}.w`` (dense) or ``l<i>.moe.router.{w,bias}``,
``l<i>.moe.experts.{gate,up,down}.w`` (stacked over the held experts) and
``l<i>.moe.shared.{gate,up,down}.w`` (the shared experts as one
feed-forward of ``num_shared_experts`` times the expert width), and
``l<i>.ffn_post_norm.w``; ``out_norm.w``, ``head.w``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

from .. import fluid
from ..fluid import layers
from ..fluid.param_attr import ParamAttr
from .cache_spec import CacheSpec
from .step_tokens import emitted_ids, fed_tokens

__all__ = ["LMConfig", "CacheSpec", "config_from_dict", "cache_specs",
           "param_shapes", "build_serve_step", "GLOBAL", "WINDOW"]

GLOBAL, WINDOW = "global", "window"
_KINDS = {"full_attention": GLOBAL, "sliding_attention": WINDOW}


class LMConfig(NamedTuple):
    vocab_size: int                 # rows held here (a slice, or all)
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int                   # queries, keys and values
    sliding_window: int
    rope_theta: float
    rms_norm_eps: float
    embedding_scale: float          # sqrt(hidden) under mup_enabled, or 1
    intermediate_size: int          # dense feed-forward
    moe_intermediate_size: int
    num_experts: int                # the router's width (all experts)
    num_shared_experts: int
    num_experts_per_tok: int
    route_scale: float
    experts_held: int
    first_expert: int
    layer_kinds: Tuple[str, ...]    # GLOBAL | WINDOW per layer
    layer_moe: Tuple[bool, ...]

    @property
    def n_layer(self) -> int:
        return len(self.layer_kinds)

    @classmethod
    def from_dict(cls, cfg: Dict) -> "LMConfig":
        """From the published keys.  ``num_experts`` counts the experts
        HELD where ``published`` states the router's width;
        ``num_hidden_layers`` takes ``layer_types``' first entries; layer
        ``i`` has experts where ``i >= num_dense_layers``."""
        for key, built in (("rope_scaling", None), ("n_group", 1),
                           ("num_expert_groups", 1), ("topk_group", 1),
                           ("num_limited_groups", 1),
                           ("score_func", "sigmoid"), ("route_norm", True)):
            if cfg.get(key, built) != built:
                raise NotImplementedError(
                    f"afmoe: {key} = {cfg[key]!r} is not built (only "
                    f"{built!r}; see the module docstring)")
        n = int(cfg["num_hidden_layers"])
        published = cfg.get("published", {})
        held = int(cfg["num_experts"])
        d = int(cfg["hidden_size"])
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=d,
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            sliding_window=int(cfg["sliding_window"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            embedding_scale=float(d) ** 0.5 if cfg.get("mup_enabled")
            else 1.0,
            intermediate_size=int(cfg["intermediate_size"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            num_experts=int(published.get("num_experts", held)),
            num_shared_experts=int(cfg.get("num_shared_experts") or 0),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            route_scale=float(cfg.get("route_scale") or 1.0),
            experts_held=held,
            first_expert=int(cfg.get("first_expert", 0)),
            layer_kinds=tuple(_KINDS[k] for k in cfg["layer_types"][:n]),
            layer_moe=tuple(i >= int(cfg["num_dense_layers"])
                            for i in range(n)))


# A decoder-only model as ``serving.paged_lm.PagedLMGenerator`` takes it
# (``models.decoder_lm`` finds this module by the published ``model_type``).
config_from_dict = LMConfig.from_dict


def cache_specs(c: LMConfig) -> Dict[str, CacheSpec]:
    """Two kinds with the same heads and widths: ``global`` (every
    position kept) and ``window`` (the last ``sliding_window``)."""
    out = {}
    for kind, window in ((GLOBAL, None), (WINDOW, c.sliding_window)):
        idx = tuple(i for i, k in enumerate(c.layer_kinds) if k == kind)
        if idx:
            out[kind] = CacheSpec(kind, idx, c.num_attention_heads,
                                  c.num_key_value_heads, c.head_dim,
                                  c.head_dim, window)
    return out


def param_shapes(c: LMConfig, prefix: str) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every parameter of the share ``c`` describes."""
    d, h, hkv, dh = (c.hidden_size, c.num_attention_heads,
                     c.num_key_value_heads, c.head_dim)
    out: Dict[str, Tuple[int, ...]] = {f"{prefix}.emb.w": (c.vocab_size, d)}
    for i, moe in enumerate(c.layer_moe):
        p = f"{prefix}.l{i}"
        out[f"{p}.attn_norm.w"] = (d,)
        out[f"{p}.attn.q.w"] = (d, h * dh)
        out[f"{p}.attn.k.w"] = (d, hkv * dh)
        out[f"{p}.attn.v.w"] = (d, hkv * dh)
        out[f"{p}.attn.gate.w"] = (d, h * dh)
        out[f"{p}.attn.out.w"] = (h * dh, d)
        out[f"{p}.attn.q_norm.w"] = (dh,)
        out[f"{p}.attn.k_norm.w"] = (dh,)
        out[f"{p}.attn_post_norm.w"] = (d,)
        out[f"{p}.ffn_norm.w"] = (d,)
        if moe:
            f, e = c.moe_intermediate_size, c.experts_held
            out[f"{p}.moe.router.w"] = (d, c.num_experts)
            out[f"{p}.moe.router.bias"] = (c.num_experts,)
            out[f"{p}.moe.experts.gate.w"] = (e, d, f)
            out[f"{p}.moe.experts.up.w"] = (e, d, f)
            out[f"{p}.moe.experts.down.w"] = (e, f, d)
            if c.num_shared_experts:
                fs = c.num_shared_experts * f
                out[f"{p}.moe.shared.gate.w"] = (d, fs)
                out[f"{p}.moe.shared.up.w"] = (d, fs)
                out[f"{p}.moe.shared.down.w"] = (fs, d)
        else:
            f = c.intermediate_size
            out[f"{p}.ffn.gate.w"] = (d, f)
            out[f"{p}.ffn.up.w"] = (d, f)
            out[f"{p}.ffn.down.w"] = (f, d)
        out[f"{p}.ffn_post_norm.w"] = (d,)
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
    documents them), for the kinds ``cache_specs`` declares.

    Every parameter is declared in the type it is resident in (matrices
    in ``dtype``; norm scales, QK-norm's among them, the router's matrix
    and selection bias float32).  The gate's sigmoid, the norms, rotary
    angles, the router's product and scores, the residual stream and the
    logits are float32."""
    specs = cache_specs(c)
    b, s_pf = int(n_lanes), int(n_prefill) * int(chunk) // int(tile)
    t = b + int(n_prefill) * int(chunk)
    n_ids = b + int(prefill_slots)      # every variant's ids, one length
    h, hkv, dh, d = (c.num_attention_heads, c.num_key_value_heads,
                     c.head_dim, c.hidden_size)
    eps = c.rms_norm_eps
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        block = prog.global_block()

        def feed(name, shape, dt="int32"):
            return layers.data(name, shape, dt, append_batch_size=False)

        tok, pos = fed_tokens(t, n_ids), feed("pos", [t])
        ring = any(spec.window is not None for spec in specs.values())
        meta = ("len", "base") + (("top",) if ring else ())
        dec = {k: feed(f"dec_{k}", [b]) for k in meta}
        pf = {k: feed(f"pf_{k}", [s_pf]) for k in meta} if s_pf else None
        cache = {}
        for kind in specs:
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
        if c.embedding_scale != 1.0:
            x = layers.scale(x, scale=c.embedding_scale)
        loads = []
        for i, kind in enumerate(c.layer_kinds):
            p, spec, kv = f"{prefix}.l{i}", specs[kind], cache[kind]
            li, nl = spec.layers.index(i), len(spec.layers)
            windowed = spec.window is not None
            u = layers.rms_norm(x, _w(f"{p}.attn_norm.w"), eps,
                                out_dtype=dtype)
            q = layers.rms_norm(
                layers.reshape(_linear(u, h * dh, f"{p}.attn.q.w"),
                               [t, h, dh]),
                _w(f"{p}.attn.q_norm.w"), eps, scope="attn/qk_norm")
            k = layers.rms_norm(
                layers.reshape(_linear(u, hkv * dh, f"{p}.attn.k.w"),
                               [t, hkv, dh]),
                _w(f"{p}.attn.k_norm.w"), eps, scope="attn/qk_norm")
            if windowed:
                # the whole head rotates; a global layer has no position
                q = layers.rotary_embedding(q, pos, dh, c.rope_theta)
                k = layers.rotary_embedding(k, pos, dh, c.rope_theta)
            v = _linear(u, hkv * dh, f"{p}.attn.v.w")
            g = _linear(u, h * dh, f"{p}.attn.gate.w")
            # every token's row first, then attention: a chunk's queries
            # read their own chunk's keys from the pool
            kv["k"] = layers.paged_row_write(kv["k"], k, kv["pages"],
                                             kv["offs"], li, nl)
            kv["v"] = layers.paged_row_write(kv["v"], v, kv["pages"],
                                             kv["offs"], li, nl)
            attn = dict(layer=li, n_layer=nl, v_pool=kv["v"],
                        window=spec.window, impl=impl, scope=f"attn/{kind}")
            parts = [q] if not s_pf else layers.split(q, [b, t - b], dim=0)
            ctx = [layers.reshape(layers.ragged_decode_attention(
                layers.reshape(parts[0], [b, 1, h, dh]), kv["k"],
                kv["dec_table"], dec["len"], dec["base"],
                ring_top=dec["top"] if windowed else None, **attn),
                [b, h * dh])]
            if s_pf:
                ctx.append(layers.reshape(layers.ragged_decode_attention(
                    layers.reshape(parts[1], [s_pf, int(tile), h, dh]),
                    kv["k"], kv["pf_table"], pf["len"], pf["base"],
                    ring_top=pf["top"] if windowed else None, **attn),
                    [t - b, h * dh]))
            o = ctx[0] if not s_pf else layers.concat(ctx, axis=0)
            o = layers.sigmoid_gate(o, g, scope="attn/gate")
            # the post norms act on a sub-block's OUTPUT, before the add
            x = layers.elementwise_add(x, layers.rms_norm(
                _linear(o, d, f"{p}.attn.out.w"),
                _w(f"{p}.attn_post_norm.w"), eps, out_dtype="float32"))
            # the router reads the float32 norm output, the products
            # the model's type
            moe = c.layer_moe[i]
            w = layers.rms_norm(x, _w(f"{p}.ffn_norm.w"), eps,
                                out_dtype="float32" if moe else dtype)
            if moe:
                y, load = layers.routed_experts(
                    w, c.num_experts, c.experts_held, c.first_expert,
                    c.num_experts_per_tok, c.moe_intermediate_size,
                    f"{p}.moe", dtype=dtype, live=live, impl=impl,
                    routed_scale=c.route_scale)
                loads.append(load)
                if c.num_shared_experts:
                    y = layers.elementwise_add(y, layers.gated_ffn(
                        w, c.num_shared_experts * c.moe_intermediate_size,
                        f"{p}.moe.shared", dtype=dtype, scope="ffn/shared"))
            else:
                y = layers.gated_ffn(w, c.intermediate_size, f"{p}.ffn",
                                     dtype=dtype, scope="ffn/dense")
            x = layers.elementwise_add(x, layers.rms_norm(
                y, _w(f"{p}.ffn_post_norm.w"), eps, out_dtype="float32"))
        last = layers.rms_norm(layers.gather(x, out_rows),
                               _w(f"{prefix}.out_norm.w"), eps,
                               out_dtype=dtype)
        logits = layers.vocab_logits(last, c.vocab_size,
                                     _w(f"{prefix}.head.w"))
        next_ids = emitted_ids(layers.argmax(logits, axis=-1),
                               b + int(n_prefill), n_ids)
        loads = layers.reshape(layers.concat(loads, axis=0),
                               [len(loads), c.experts_held]) \
            if loads else None
    return prog, startup, next_ids, logits, loads

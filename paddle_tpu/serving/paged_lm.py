"""Paged serving of a DECODER-ONLY language model whose layers keep
different caches: ``PagedLMGenerator``.

Where ``PagedTransformerGenerator`` serves the encoder-decoder Transformer
(a prompt in encoder pages, generation in self pages, one logical page
spanning every layer), this generator serves a model in which prompt and
generation share one context and the layers come in KINDS (the model's
``cache_specs``): global-attention layers keep every position,
sliding-window layers the last ``window``.  The model is a BUILDER the
generator is given or finds by the published ``model_type``
(``models.decoder_lm``): ``config_from_dict``, ``cache_specs``,
``param_shapes``, ``build_serve_step``; no model is named here.  What a
builder may vary per kind of layer without the engine knowing: the
rotary embedding (part of a head, all of it, or none: a global layer
without positions), a norm on every head of the queries and keys before
the key row is written (QK-norm), a gate on the attention's output, norms
around a sub-block; the engine sees each kind's pools, tables and lengths
(``models.cache_spec``).  So a request holds
pages of one GROUP a kind, each with its own pool or pool pair, allocator,
table and accounting (``paging.PageGroup``).  A kind whose values are the
leading columns of its key row (latent attention: ``CacheSpec.latent``)
has ONE pool, allocated, donated, counted and reported as one; every
other kind a key pool and a value pool.

* **global** pages grow with the context: table slot = position // page;
* **window** pages are a RING: slot = (position // page) % ring width,
  taken when the context reaches them and given back as soon as they lie
  behind ``position - window`` — a 1000-token generation holds a bounded
  number of them.

Admission reserves a request's worst case in EVERY group, so a running
request never waits for a page.

One compiled dispatch a step, over a FLAT batch of tokens: one decode
token per lane, then up to ``prefill_slots`` prompt chunks of
``chunk_size`` tokens, each of another lane, oldest admission first.  A
step pays for the chunks it carries and no more: there is one program per
number of chunks (0 .. ``prefill_slots``), all resolved by ``aot_warm``.
Products over the flat batch read every weight once for decode and prefill
rows together; only attention tells them apart.

The scheduler-facing surface is ``PagedTransformerGenerator``'s
(``pages_needed / can_admit / prompt_infeasible / open_slots / admit_slot /
tag_slot / clear_slot / lane_step / cache_stats / aot_warm``; ``src_len``
is the prompt cap), and ``lane_step`` opens the same spans.  Refused, with
an error that says so: prefix sharing (a window layer's pages are gone when
a second request could share them), beam search, speculative decoding,
session suspend / resume, int8 pools and a ``mesh_axes`` artifact.

**One step of lookahead.**  A step is two halves: ``_launch`` (the feed,
the dispatch, and everything the engine books that does not depend on a
token's VALUE: positions, prompt progress, which row of the step's ids is
which lane's) and ``_collect`` (the fetch, which waits for the device, the
counters that read it, the tokens).  ``lane_step`` is the two in a row.
``lane_step_ahead`` is what a scheduler's loop calls instead: launch step
n+1, THEN collect step n, so the host's part of a step runs while the
device runs the step before.  What makes that sound here is what the
generator refuses: nothing reads a token on the host between two steps but
the next step's input, and a decode row whose token is still in flight
reads it on the device, from the last step's ids (``models.step_tokens``).
The caller sees a lane's tokens one call late; a lane cleared in between
has its token dropped here (``clear_slot`` moves the lane's epoch on).
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import fluid
from ..kernels.flash_attention import latent_row_width, split_query_tile
from ..models import decoder_lm
from ..observability import tracing as _obs_tracing
from .paged_common import ceil_div, token_slots, zero_pool
from .paging import PageGroup, PoolCapacityError, TRASH_PAGE

__all__ = ["PagedLMGenerator", "lm_pool_layout", "estimate_lm_hbm",
           "LM_CONFIG_KEYS"]

_KV_ITEMSIZE = {"float32": 4, "bfloat16": 2}

# the manifest keys of a ``kind: "lm_generator"`` artifact
LM_CONFIG_KEYS = (
    "model", "param_prefix", "src_len", "max_out_len", "lanes", "page_size",
    "window_page_size", "num_pages", "window_pages", "chunk_size",
    "prefill_slots", "kv_dtype", "dtype", "start_id", "end_id",
    "prefix_sharing", "mesh_axes", "attn_impl")


def _refuse(what: str) -> "NotImplementedError":
    return NotImplementedError(
        f"PagedLMGenerator does not support {what} (a decoder-only model "
        f"with window layers; see its module docstring)")


def lm_pool_layout(config: Dict, builder=None) -> Dict:
    """Everything static about the pools a manifest ``config`` describes:
    the model, and per kind of layer the page size, the table's width, the
    number of pages and the names and shapes of its pool (``k`` alone, a
    latent kind) or pool pair (``k`` and ``v``).  Shared by the
    generator's constructor and the registry's HBM estimate, so the two
    cannot disagree.  ``builder`` (default: the one the model's
    ``model_type`` names) is the model, as the module docstring says."""
    if builder is None:
        kind = config["model"].get("model_type")
        if kind is None:
            raise ValueError("lm_generator: the manifest's model has no "
                             "'model_type' and no builder was given")
        builder = decoder_lm(str(kind))
    c = builder.config_from_dict(config["model"])
    specs = builder.cache_specs(c)
    prefix = str(config.get("param_prefix", "lm"))
    src_len = int(config.get("src_len", 64))
    max_out = int(config.get("max_out_len", 64))
    lanes = int(config.get("lanes", 8))
    chunk = int(config.get("chunk_size", 8))
    kv_dtype = str(config.get("kv_dtype", "float32"))
    if kv_dtype not in _KV_ITEMSIZE:
        raise _refuse(f"kv_dtype {kv_dtype!r} (float32 or bfloat16 pools "
                      f"only: no int8 pool)")
    max_context = src_len + max_out
    groups = {}
    for kind, spec in specs.items():
        if spec.window is None:
            ps = int(config.get("page_size", 16))
            table = ceil_div(max_context, ps)
            pages = config.get("num_pages")
            pages = lanes * table + 1 if pages is None else int(pages)
            decode_pages = None
        else:
            ps = int(config.get("window_page_size")
                     or config.get("page_size", 16))
            # a step writes its chunk and then reads (first query -
            # window, last query]: that many pages are live at once
            table = ceil_div(chunk + spec.window - 2, ps) + 1
            decode_pages = ceil_div(spec.window - 1, ps) + 1
            pages = config.get("window_pages")
            pages = lanes * table + 1 if pages is None else int(pages)
        rows = pages * len(spec.layers)
        # a latent row is allocated in whole lane tiles
        k_width = latent_row_width(spec.d_key) if spec.latent \
            else spec.kv_heads * spec.d_key
        groups[kind] = {
            "spec": spec, "page_size": ps, "table": table,
            "num_pages": pages, "decode_pages": decode_pages,
            "dtype": kv_dtype,
            "k": f"{prefix}@kv_pool.{kind}.k",
            "k_shape": [rows, ps, k_width]}
        if not spec.latent:
            groups[kind].update(
                v=f"{prefix}@kv_pool.{kind}.v",
                v_shape=[rows, ps, spec.kv_heads * spec.d_value])
    # prefill attention runs in tiles of queries, each a lane of the
    # ragged kernel: as many as the kernel's fast memory takes, of the
    # kind of layer that takes fewest
    tile = min(split_query_tile(
        chunk, g["spec"].q_heads, g["spec"].kv_heads,
        g["k_shape"][2] // g["spec"].kv_heads, g["spec"].d_value,
        g["page_size"], _KV_ITEMSIZE[kv_dtype], latent=g["spec"].latent)
        for g in groups.values())
    return {"builder": builder, "model": c, "prefix": prefix,
            "src_len": src_len, "max_out_len": max_out, "lanes": lanes,
            "chunk": chunk, "groups": groups, "kv_dtype": kv_dtype,
            "dtype": str(config.get("dtype", "float32")),
            "prefill_slots": int(config.get("prefill_slots", 1)),
            "tile": tile, "impl": config.get("attn_impl")}


def _pools(group: Dict):
    """(name, shape) of a kind's pool, or of each of its pool pair."""
    return [(group[p], group[f"{p}_shape"]) for p in ("k", "v")
            if p in group]


def _build(layout: Dict, n_prefill: int):
    return layout["builder"].build_serve_step(
        layout["model"], prefix=layout["prefix"], pools=layout["groups"],
        n_lanes=layout["lanes"], n_prefill=n_prefill,
        prefill_slots=layout["prefill_slots"], chunk=layout["chunk"],
        tile=layout["tile"], dtype=layout["dtype"],
        impl=layout["impl"])


def estimate_lm_hbm(config: Dict, builder=None):
    """Static peak-HBM plan of the largest serve step a manifest config
    describes (every prefill slot full), from its DESC: parameters in the
    type they are resident in, every kind's pool or pool pair, and the
    step's activations.  No device allocation."""
    from ..fluid.analysis.cost import plan_program

    if config.get("mesh_axes"):
        raise _refuse("a mesh_axes artifact")
    layout = lm_pool_layout(config, builder)
    prog = _build(layout, layout["prefill_slots"])[0]
    return plan_program(prog, assume_batch=1)


class _Lane:
    __slots__ = ("phase", "prompt", "done", "pos", "cur", "ahead", "left",
                 "epoch", "rid", "pages", "tables", "chunk")

    def __init__(self):
        self.epoch = 0                  # requests this lane has dropped
        self.reset()

    def reset(self):
        self.phase = "idle"             # idle | prefill | decode
        self.prompt = None
        self.done = 0                   # prompt tokens in the cache
        self.pos = 0                    # tokens in the cache
        self.cur = 0                    # the next decode step's input ...
        # ... unless it is still in flight: (step, row of that step's ids)
        self.ahead = None
        self.left = 0                   # tokens it may yet be launched for
        self.rid = None
        self.pages: Dict[str, Dict[int, int]] = {}  # kind -> logical -> page
        self.tables: Dict[str, np.ndarray] = {}     # kind -> its feed row
        self.chunk = 0                  # prompt tokens in flight


class _Flight(NamedTuple):
    """A launched step nobody has fetched yet."""
    step: int
    ids: object                 # [lanes + prefill_slots], on the device
    load: object                # [expert layers, held] on the device, None
    logits: object              # on the device; None unless asked for
    rows: Dict[int, Tuple[int, int]]    # slot -> (row of ids, lane's epoch)


class PagedLMGenerator:
    """See the module docstring."""

    page_aware = True

    def __init__(self, model: Dict, *, builder=None, param_prefix="lm",
                 src_len=64, max_out_len=64, lanes=8, page_size=16,
                 window_page_size=None, num_pages=None, window_pages=None,
                 chunk_size=8, prefill_slots=1, kv_dtype="float32",
                 dtype="float32", start_id=0, end_id=1,
                 prefix_sharing=False, mesh_axes=None, attn_impl=None,
                 scope=None, executor=None, place=None):
        if prefix_sharing:
            raise _refuse("prefix sharing (prefix_sharing must be false)")
        if mesh_axes:
            raise _refuse("a mesh_axes artifact")
        config = dict(model=model, param_prefix=param_prefix,
                      src_len=src_len, max_out_len=max_out_len, lanes=lanes,
                      page_size=page_size, window_page_size=window_page_size,
                      num_pages=num_pages, window_pages=window_pages,
                      chunk_size=chunk_size, prefill_slots=prefill_slots,
                      kv_dtype=kv_dtype, dtype=dtype, attn_impl=attn_impl)
        self.config = config
        self.layout = lay = lm_pool_layout(config, builder)
        self.builder = lay["builder"]
        self.model = lay["model"]
        self.prefix = lay["prefix"]
        self.src_len = lay["src_len"]           # the prompt cap
        self.max_out_len = lay["max_out_len"]
        self.lanes = lay["lanes"]
        self.chunk, self.tile = lay["chunk"], lay["tile"]
        self.prefill_slots = lay["prefill_slots"]
        self.kv_dtype = kv_dtype
        self.start_id, self.end_id = int(start_id), int(end_id)
        self.prefix_sharing = False
        self.scope = scope or fluid.Scope()
        self.exe = executor or fluid.Executor(place or fluid.TPUPlace(0))
        self._tracer = _obs_tracing.tracer()
        self.groups: Dict[str, PageGroup] = {
            kind: PageGroup(kind, g["num_pages"], g["page_size"])
            for kind, g in lay["groups"].items()}
        self._steps_built = {n: _build(lay, n)
                             for n in range(self.prefill_slots + 1)}
        # the window group's page size: a lane's newest window page is
        # position // this
        self._ring_ps = next((g["page_size"]
                              for g in lay["groups"].values()
                              if g["decode_pages"] is not None), 1)
        self._lanes: List[_Lane] = []
        self._queue: deque = deque()            # slots waiting to prefill
        self._slots = 0
        self._steps = 0
        self._pairs = 0
        self._touched = 0       # (step, layer, expert) with a pair or more
        # layers that keep a latent row a token, and the rows written
        self._latent_layers = sum(len(g["spec"].layers)
                                  for g in lay["groups"].values()
                                  if g["spec"].latent)
        self._latent_rows = 0
        # what the steps held of prefill: prompt tokens, and the chunks
        # that carried them
        self._prompt_tokens = 0
        self._chunks = 0
        # launched and not fetched yet, oldest first: none between two
        # ``lane_step`` calls, at most one between two ``lane_step_ahead``
        self._in_flight: deque = deque()
        self._steps_ahead = 0       # launched while one was in flight
        self._fed_on_device = 0     # decode rows whose input was
        self._strays = 0            # tokens of lanes cleared in flight
        import jax.numpy as jnp

        # the newest launch's ids, which every step is fed
        self._ids = jnp.zeros(self.lanes + self.prefill_slots, "int32")
        loads = self._steps_built[0][4]     # [expert layers, held] or None
        self._load = np.zeros([int(n) for n in loads.shape]
                              if loads is not None else (0, 0), np.int64)
        self._reset_pools()

    # -- pools ---------------------------------------------------------------
    def _reset_pools(self) -> None:
        for g in self.layout["groups"].values():
            for name, shape in _pools(g):
                zero_pool(self.scope, name, shape, g["dtype"])

    def param_dtypes(self) -> Dict[str, str]:
        """name -> the type the step program declares each parameter in:
        what it is resident in (matrices in ``dtype``; norm scales, sinks,
        a router's matrix and selection bias float32)."""
        block = self._steps_built[0][0].global_block()
        return {name: str(block.var(name).dtype) for name in
                self.builder.param_shapes(self.model, self.prefix)}

    def load_weights(self, weights: Dict[str, object]) -> None:
        """Put ``weights`` (name -> array, float32 masters) into the scope
        in the types the step keeps them in (``param_dtypes``)."""
        import jax.numpy as jnp

        want = self.builder.param_shapes(self.model, self.prefix)
        dtypes = self.param_dtypes()
        for name, shape in want.items():
            value = jnp.asarray(weights[name])
            if tuple(value.shape) != tuple(shape):
                raise ValueError(f"{name}: shape {tuple(value.shape)}, the "
                                 f"model's is {tuple(shape)}")
            self.scope.set_var(name, value.astype(dtypes[name]))

    # -- admission -----------------------------------------------------------
    def _resolve_max_new(self, max_new: Optional[int]) -> int:
        m = self.max_out_len if max_new is None else int(max_new)
        if m > self.max_out_len:
            raise ValueError(f"max_new {m} exceeds max_out_len "
                             f"{self.max_out_len}")
        return max(1, m)

    def pages_needed(self, src_tokens, max_new: Optional[int] = None
                     ) -> Dict[str, int]:
        """Pages a request reserves, by group: its whole context's global
        pages, and the window ring a prefilling lane walks."""
        n = len(np.asarray(src_tokens).reshape(-1)) \
            + self._resolve_max_new(max_new)
        return {kind: ceil_div(n, g["page_size"])
                if g["decode_pages"] is None else g["table"]
                for kind, g in self.layout["groups"].items()}

    def can_admit(self, src_tokens, max_new: Optional[int] = None) -> bool:
        return all(self.groups[k].can_reserve(n) for k, n in
                   self.pages_needed(src_tokens, max_new).items())

    def prompt_infeasible(self, src_tokens,
                          max_new: Optional[int] = None) -> bool:
        """Never admissible: more pages than a whole group holds."""
        return any(n > self.groups[k].total_usable for k, n in
                   self.pages_needed(src_tokens, max_new).items())

    def open_slots(self, n_slots: int) -> None:
        if int(n_slots) != self.lanes:
            raise ValueError(
                f"open_slots({n_slots}): this generator's step is built "
                f"for {self.lanes} lanes (manifest key 'lanes')")
        for slot in range(self._slots):
            self.clear_slot(slot)
        self._slots = self.lanes
        self._lanes = [_Lane() for _ in range(self.lanes)]
        self._queue.clear()
        self._in_flight.clear()

    def admit_slot(self, slot: int, src_tokens_1d,
                   max_new: Optional[int] = None) -> int:
        src = np.asarray(src_tokens_1d).reshape(-1).astype(np.int64)
        if not 1 <= len(src) <= self.src_len:
            raise ValueError(f"prompt of {len(src)} tokens; the model "
                             f"takes 1..{self.src_len}")
        if src.min() < 0 or src.max() >= self.model.vocab_size:
            raise ValueError("prompt ids outside the vocabulary held here "
                             f"(0..{self.model.vocab_size - 1})")
        lane = self._lanes[slot]
        if lane.phase != "idle":
            raise RuntimeError(f"slot {slot} is busy")
        need = self.pages_needed(src, max_new)
        if not all(self.groups[k].can_reserve(n) for k, n in need.items()):
            raise PoolCapacityError(
                f"slot {slot}: pages {need} asked, unreserved "
                f"{ {k: g.unreserved() for k, g in self.groups.items()} }")
        for kind, n in need.items():
            self.groups[kind].reserve(slot, n)
        lane.phase, lane.prompt = "prefill", src
        lane.left = self._resolve_max_new(max_new)
        lane.pages = {kind: {} for kind in self.groups}
        self._queue.append(slot)
        return len(src)

    def tag_slot(self, slot: int, rid: int) -> None:
        self._lanes[slot].rid = int(rid)

    def clear_slot(self, slot: int) -> None:
        lane = self._lanes[slot]
        if lane.phase == "idle":
            return
        for kind, held in lane.pages.items():
            self.groups[kind].release(slot, list(held.values()))
        if slot in self._queue:
            self._queue.remove(slot)
        lane.reset()
        lane.epoch += 1         # its tokens in flight are nobody's now
        if self._in_flight and all(ln.phase == "idle"
                                   for ln in self._lanes):
            # nobody is left to take a token of the steps in flight
            self._strays += sum(len(f.rows) for f in self._in_flight)
            self._in_flight.clear()

    # -- refused -------------------------------------------------------------
    def resume_slot(self, slot, session_id, max_new=None):
        raise _refuse("session suspend / resume")

    def detach_slot(self, slot, session_id):
        raise _refuse("session suspend / resume")

    def beam(self, *args, **kwargs):
        raise _refuse("beam search")

    # -- a step's feeds ------------------------------------------------------
    def _pages_for(self, slot: int, lane: _Lane, kind: str, first: int,
                   last: int) -> None:
        """Lane ``slot`` is about to write positions first..last: take the
        pages they reach and, in a window group, give back those no query
        from ``first`` on can see."""
        g, group = self.layout["groups"][kind], self.groups[kind]
        held, ps = lane.pages[kind], g["page_size"]
        window = g["spec"].window
        before = len(held), group.taken
        if window is not None:
            oldest = (first - window + 1) // ps     # the oldest page read
            for page in [p for p in held if p < oldest]:
                group.give(slot, held.pop(page))
        for page in range(first // ps, last // ps + 1):
            if page not in held:
                held[page] = group.take(slot)
        if before != (len(held), group.taken):
            lane.tables.pop(kind, None)

    def _table(self, lane: _Lane, kind: str) -> np.ndarray:
        """The lane's page table as its feed row (a ring's slot is the
        page modulo the ring), kept until its pages change."""
        row = lane.tables.get(kind)
        if row is None:
            g = self.layout["groups"][kind]
            row = np.full(g["table"], TRASH_PAGE, np.int32)
            ring = g["decode_pages"] is not None
            for page, phys in lane.pages[kind].items():
                row[page % g["table"] if ring else page] = phys
            lane.tables[kind] = row
        return row

    def _feed(self, chosen: List[int], n_pf: Optional[int] = None):
        """The feed of a step that carries ``chosen``'s next chunks, sized
        for ``n_pf`` chunks (default: as many as chosen); rows of no lane
        write the trash page and read nothing."""
        B, C, tq = self.lanes, self.chunk, self.tile
        n_pf = len(chosen) if n_pf is None else int(n_pf)
        T, S = B + n_pf * C, n_pf * C // tq
        kinds = self.layout["groups"]
        feed = {"tok": np.zeros(T, np.int64), "pos": np.zeros(T, np.int32),
                "prev_ids": self._ids,
                "tok_src": np.arange(T, dtype=np.int32),
                "out_rows": np.arange(B + n_pf, dtype=np.int32)}
        if self._load.size:
            # the rows that are a request's tokens: the others route to
            # no expert
            feed["live"] = np.zeros(T, np.int32)
        # ``top`` (a lane's newest window page) where a kind is a ring
        ring = any(g["decode_pages"] is not None for g in kinds.values())
        for k in ("len", "base") + (("top",) if ring else ()):
            feed[f"dec_{k}"] = np.zeros(B, np.int32)
            if n_pf:
                feed[f"pf_{k}"] = np.zeros(S, np.int32)
        for kind, g in kinds.items():
            feed[f"{kind}_pages"] = np.full(T, TRASH_PAGE, np.int32)
            feed[f"{kind}_offs"] = np.zeros(T, np.int32)
            feed[f"dec_{kind}_table"] = np.zeros((B, g["table"]), np.int32)
            if n_pf:
                feed[f"pf_{kind}_table"] = np.zeros((S, g["table"]),
                                                    np.int32)
        ring_ps = self._ring_ps
        decoding = []
        for slot, lane in enumerate(self._lanes):
            # a lane is launched for ``max_new`` tokens and no more: the
            # last position it writes is prompt + max_new - 2, inside the
            # prompt + max_new positions admission reserved.  THIS rule is
            # what keeps every fed page inside a lane's reservation, a
            # step ahead too: the one row launched after a token that
            # turns out to be the end of the sequence is inside the cap
            # like any other (and a page a cleared lane gave back is
            # written by its next holder in a LATER launch, which the
            # device runs after)
            if lane.phase != "decode" or not lane.left:
                continue
            t = lane.pos
            decoding.append(slot)
            feed["pos"][slot] = t
            if lane.ahead is None:
                feed["tok"][slot] = lane.cur
            else:
                feed["tok_src"][slot] = T + lane.ahead[1]
                self._fed_on_device += 1
            if "live" in feed:
                feed["live"][slot] = 1
            feed["dec_len"][slot], feed["dec_base"][slot] = t + 1, t
            if ring:
                feed["dec_top"][slot] = t // ring_ps
            for kind, g in kinds.items():
                ps = g["page_size"]
                if t % ps == 0 or t // ps not in lane.pages[kind]:
                    self._pages_for(slot, lane, kind, t, t)
                feed[f"{kind}_pages"][slot] = lane.pages[kind][t // ps]
                feed[f"{kind}_offs"][slot] = t % ps
                feed[f"dec_{kind}_table"][slot] = self._table(lane, kind)
        for s, slot in enumerate(chosen):
            lane = self._lanes[slot]
            done = lane.done
            m = min(C, len(lane.prompt) - done)
            lane.chunk = m
            rows = slice(B + s * C, B + s * C + m)
            positions = done + np.arange(m)
            feed["tok"][rows] = lane.prompt[done:done + m]
            if "live" in feed:
                feed["live"][rows] = 1
            feed["pos"][rows] = positions
            feed["out_rows"][B + s] = B + s * C + m - 1
            tiles = slice(s * C // tq, s * C // tq + ceil_div(m, tq))
            base = done + tq * np.arange(tiles.stop - tiles.start)
            feed["pf_base"][tiles] = base
            feed["pf_len"][tiles] = np.minimum(done + m, base + tq)
            if ring:
                feed["pf_top"][tiles] = (done + m - 1) // ring_ps
            for kind, g in kinds.items():
                ps, held = g["page_size"], lane.pages[kind]
                self._pages_for(slot, lane, kind, done, done + m - 1)
                pages = np.zeros(positions[-1] // ps + 1, np.int32)
                for page, phys in held.items():
                    pages[page] = phys
                feed[f"{kind}_pages"][rows], feed[f"{kind}_offs"][rows] = \
                    token_slots(pages, positions, ps)
                feed[f"pf_{kind}_table"][tiles] = self._table(lane, kind)
        return feed, decoding

    # -- the step ------------------------------------------------------------
    def lane_step(self) -> Dict[int, int]:
        """ONE dispatch: every decoding lane emits a token, and up to
        ``prefill_slots`` prefilling lanes (oldest admission first)
        advance one chunk; a lane whose prompt ends in this step emits its
        first token.  Returns {slot: token}."""
        return self._step(False)[0]

    def step_logits(self):
        """``lane_step`` that also returns the float32 logits behind each
        emitted token: ({slot: token}, {slot: [vocab]}).  For tests."""
        return self._step(True)

    def lane_step_ahead(self) -> Dict[int, object]:
        """``lane_step`` one step ahead: launch the NEXT step, then fetch
        the one launched a call ago, so the feed, the dispatch and whatever
        the caller does between two calls run while the device does.
        Returns that step's {slot: token}: a lane's tokens come one call
        late, in order, the same tokens.  From an idle engine two steps
        are launched before the first is fetched; a launch after which no
        lane is left to launch has nothing to hide its wait behind and is
        fetched in the same call, so a lane may get its last two tokens at
        once, as a list, and an engine whose lanes ran to their caps is
        left with nothing in flight."""
        if not self._in_flight:
            self._launch()
        if self._can_launch():
            self._launch()
        emitted: Dict[int, object] = self._collect()
        if self._in_flight and not self._can_launch():
            for slot, tok in self._collect().items():
                emitted[slot] = [emitted[slot], tok] if slot in emitted \
                    else tok
        return emitted

    def _step(self, want_logits: bool):
        if self._in_flight:
            raise RuntimeError(
                "a step launched by lane_step_ahead() is in flight: this "
                "call would fetch out of turn")
        self._launch(want_logits)
        flight = self._in_flight[0]
        emitted = self._collect()
        if not want_logits:
            return emitted, None
        lg = np.asarray(flight.logits)
        return emitted, {slot: lg[flight.rows[slot][0]] for slot in emitted}

    def _can_launch(self) -> bool:
        """Would a step carry a lane's row?"""
        return bool(self._queue) or any(
            lane.phase == "decode" and lane.left for lane in self._lanes)

    def _launch(self, want_logits: bool = False) -> None:
        """Feed and dispatch a step, and book everything about it that is
        known without its tokens.  Nothing here waits for the device."""
        if self._slots == 0:
            raise RuntimeError("open_slots() before lane_step()")
        tr = self._tracer
        with tr.span("engine/feed_build", cat="serving"):
            chosen = list(self._queue)[:self.prefill_slots]
            feed, decoding = self._feed(chosen)
            prog, _, next_ids, logits, loads = \
                self._steps_built[len(chosen)]
            fetch = [next_ids] + ([loads] if loads is not None else []) \
                + ([logits] if want_logits else [])
        with tr.span("engine/dispatch", cat="serving"), \
                fluid.scope_guard(self.scope):
            out = self.exe.run(prog, feed=feed, fetch_list=fetch,
                               return_numpy=False, mode="infer")
        with tr.span("engine/absorb", cat="serving"):
            self._steps += 1
            self._steps_ahead += bool(self._in_flight)
            self._ids = out[0]
            rows: Dict[int, int] = {}
            written = len(decoding)
            for slot in decoding:
                self._lanes[slot].pos += 1
                rows[slot] = slot
            for s, slot in enumerate(chosen):
                lane = self._lanes[slot]
                who = {} if lane.rid is None else {"rid": lane.rid}
                tr.instant("lane/prefill_chunk", cat="serving", slot=slot,
                           tokens=lane.chunk, done=lane.done + lane.chunk,
                           total=len(lane.prompt), **who)
                written += lane.chunk
                self._prompt_tokens += lane.chunk
                lane.done += lane.chunk
                lane.pos = lane.done
                lane.chunk = 0
                if lane.done >= len(lane.prompt):
                    self._finish_prefill(slot, lane)
                    rows[slot] = self.lanes + s
            self._chunks += len(chosen)
            self._latent_rows += written * self._latent_layers
            for slot, row in rows.items():
                lane = self._lanes[slot]
                lane.left -= 1
                lane.ahead = (self._steps, row)
            self._in_flight.append(_Flight(
                self._steps, out[0], out[1] if loads is not None else None,
                out[-1] if want_logits else None,
                {slot: (row, self._lanes[slot].epoch)
                 for slot, row in rows.items()}))

    def _collect(self) -> Dict[int, int]:
        """Fetch the oldest step in flight: {slot: token} of the lanes that
        emitted in it and are still the request they were."""
        flight = self._in_flight.popleft()
        tr = self._tracer
        with tr.span("engine/fetch", cat="serving"):
            # the host blocks here until the device has finished the step
            ids = np.asarray(flight.ids).reshape(-1)
            load = None if flight.load is None else \
                np.asarray(flight.load).reshape(self._load.shape)
        emitted: Dict[int, int] = {}
        with tr.span("engine/absorb", cat="serving"):
            if load is not None:
                self._load += load
                self._pairs += int(load.sum())
                self._touched += int(np.count_nonzero(load))
            for slot, (row, epoch) in flight.rows.items():
                lane = self._lanes[slot]
                if lane.epoch != epoch:
                    self._strays += 1       # cleared since the launch
                    continue
                emitted[slot] = tok = int(ids[row])
                if lane.ahead == (flight.step, row):
                    # no later launch has taken it from the device
                    lane.cur, lane.ahead = tok, None
        return emitted

    def _finish_prefill(self, slot: int, lane: _Lane) -> None:
        lane.phase = "decode"
        self._queue.remove(slot)
        for kind, g in self.layout["groups"].items():
            if g["decode_pages"] is None:
                continue
            # a decoding lane walks fewer window pages than a chunk spans:
            # give the rest of the ring back to admission
            self._pages_for(slot, lane, kind, lane.pos, lane.pos - 1)
            self.groups[kind].shrink(
                slot, max(g["decode_pages"], len(lane.pages[kind])))

    # -- warm-up, accounting -------------------------------------------------
    def step_variants(self) -> List[int]:
        return sorted(self._steps_built)

    def aot_warm(self, n_slots: int) -> None:
        """Resolve EVERY step program (0 .. ``prefill_slots`` chunks) at
        the serving lane count with all-idle dispatches: dead rows write
        the trash page and read nothing, so no cache or lane state
        changes."""
        if any(lane.phase != "idle" for lane in self._lanes):
            raise RuntimeError("aot_warm: lanes are busy")
        self.open_slots(int(n_slots))
        for n_pf, (prog, _, next_ids, _lg, loads) in sorted(
                self._steps_built.items()):
            feed, _ = self._feed([], n_pf)
            fetch = [next_ids] + ([loads] if loads is not None else [])
            with fluid.scope_guard(self.scope):
                # each is fed the ids of the one before, as in service
                self._ids = self.exe.run(
                    prog, feed=feed, fetch_list=fetch, return_numpy=False,
                    mode="infer")[0]

    def kv_bytes_per_token(self) -> int:
        """Bytes a cached token costs, as allocated, in the layers that
        keep it for good (the global group; a pool's or a pool pair's row
        width a layer); a window layer's cost does not grow with the
        context."""
        item = _KV_ITEMSIZE[self.kv_dtype]
        return sum(len(g["spec"].layers) * item
                   * sum(shape[2] for _, shape in _pools(g))
                   for g in self.layout["groups"].values()
                   if g["decode_pages"] is None)

    def static_hbm_estimate(self, assume_lanes: int = None):
        cached = getattr(self, "_hbm_plan", None)
        if cached is None:
            cached = self._hbm_plan = estimate_lm_hbm(self.config)
        return cached

    def counters(self) -> Dict[str, object]:
        """What the step has done since load, for ``sched.stats()`` and
        the benchmark's per-layer readers."""
        out = {"steps": self._steps, "steps_ahead": self._steps_ahead,
               "tokens_fed_on_device": self._fed_on_device,
               "stray_tokens_dropped": self._strays,
               "prompt_tokens_prefilled": self._prompt_tokens,
               "prefill_chunks": self._chunks,
               "moe_pairs_here": self._pairs,
               "experts_touched": self._touched,
               "expert_load": self._load.tolist(),
               "kv_bytes_per_token": self.kv_bytes_per_token()}
        if self._latent_layers:
            # (token, layer) rows scattered into a latent kind's one pool
            out["latent_rows_written"] = self._latent_rows
        for kind, group in self.groups.items():
            st = group.stats()
            out[f"{kind}_pages_in_use"] = st["in_use"]
            out[f"{kind}_pages"] = group.total_usable
            out[f"{kind}_pages_recycled"] = st["recycled"]
        return out

    def cache_stats(self) -> Dict[str, object]:
        item = _KV_ITEMSIZE[self.kv_dtype]
        pool_bytes = {
            kind: sum(int(np.prod(shape)) for _, shape in _pools(g)) * item
            for kind, g in self.layout["groups"].items()}
        return {"executable": self.exe.cache_stats()["executable"],
                "pages": {k: g.stats() for k, g in self.groups.items()},
                "steps": self._steps,
                "counters": self.counters(),
                "hbm": {"kv_dtype": self.kv_dtype,
                        "pool_bytes": pool_bytes,
                        "kv_bytes_per_token": self.kv_bytes_per_token()}}

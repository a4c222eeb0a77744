"""Paged-KV Transformer serving: block-table page indirection, chunked
prefill, and continuous batching in ONE compiled dispatch.

``TransformerGenerator`` (PR 5) provisions dense per-lane caches —
``[B, src_len, h, d]`` cross K/V plus ``[B, max_out_len, h, d]`` self
K/V per layer — so HBM is reserved for the worst case whether or not a
request uses it, and decode attention reads padded garbage bytes.
``PagedTransformerGenerator`` replaces that with the Ragged-Paged-
Attention model (PAPERS.md, arxiv 2604.15464):

* **one pooled KV tensor** ``[R, page_size, h*d]`` shared by every
  lane, layer, and role (encoder-KV, cross-KV, decoder-self-KV) — a
  logical page spans all layers and K+V of a page_size-token span, and
  a token is one row the donated step writes in place;
* **per-request page tables** allocated/freed by the host-side
  ``PageAllocator`` and fed as int32 data (a new page id never
  recompiles anything);
* **chunked prefill**: the source is encoded CAUSALLY in fixed-size
  chunks through the SAME compiled program that decodes in-flight
  lanes — admission no longer stalls decode behind a monolithic
  prefill dispatch, and there is no separate prefill executable to
  warm (feed the dense baseline ``make_attn_bias(..., causal=True)``
  for exact parity).  The prefill half is fed one row per lane that
  PREFILLS, padded to one of a few widths derived from the lane count
  (``tower_widths``: an eighth of the lanes, or all of them), so a
  step whose lanes mostly decode does not encode, project and
  page-write a chunk of dead rows for each of them; each width is
  one executable of the same program, all resolved at load;
* **prefix sharing**: full prompt chunks are content-addressed
  (chain hashes) so identical prompt prefixes — a common system
  prompt — map to the same physical pages with refcounts; beam lanes
  share parent pages after each reorder with copy-on-write instead of
  the dense path's whole-cache ``batch_gather`` copy.

The dense decoder stays as the differential baseline: greedy is
token-for-token and beam score-for-score identical (tests/
test_paged_serving.py) when both run the causal-encoder feeds.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import fluid
from ..fluid import layers
from ..fluid.core.lod import SeqArray
from ..observability import tracing as _obs_tracing
from ..models import transformer as T
from .decoder import _Cfg, dense_kv_bytes_per_slot
from .paged_common import (ceil_div, pool_variable, token_slots,
                           zero_pool)
from .paging import (PageAllocator, PoolCapacityError, TRASH_PAGE,
                     chunk_hashes)

__all__ = ["PagedTransformerGenerator", "copy_weights", "kv_page_bytes",
           "build_unified_program", "build_manifest_program",
           "estimate_generator_hbm", "default_num_pages",
           "model_axis_of", "check_shardable", "tower_widths",
           "unified_bucket_set", "TOWER_FEEDS"]




_KV_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def kv_page_bytes(n_layer: int, n_head: int, d_head: int, page_size: int,
                  kv_dtype: str = "float32") -> int:
    """HBM bytes ONE logical page costs: ``2 * n_layer`` physical rows of
    ``[page_size, n_head * d_head]`` K/V in ``kv_dtype``, plus — for int8
    pools — the fp32 block scale each (row, slot) carries in the sidecar.
    The single bytes formula the generator, bench.py's capacity contest,
    and the scheduler's HBM accounting all share (ISSUE 7: the int8
    halving must be visible in one number, not re-derived per caller)."""
    if kv_dtype not in _KV_ITEMSIZE:
        raise ValueError(f"kv_page_bytes: unsupported kv_dtype "
                         f"{kv_dtype!r} (one of {sorted(_KV_ITEMSIZE)})")
    rows = 2 * n_layer
    data = rows * page_size * n_head * d_head * _KV_ITEMSIZE[kv_dtype]
    scales = rows * page_size * 4 if kv_dtype == "int8" else 0
    return data + scales


# decode-time cache state (paged pool + sidecar, dense per-lane caches):
# never weights, so never copy_weights material — carrying them across
# scopes would drag stale cache contents (and for the pool, the wrong
# dtype) into the destination generator
_CACHE_MARKERS = ("@kv_pool", "@kv_scales", "@kcache", "@vcache",
                  "@crossk", "@crossv")


def copy_weights(src_scope, dst_scope, prefix: Optional[str] = None,
                 dst_prefix: Optional[str] = None) -> int:
    """Host-copy vars from ``src_scope`` into ``dst_scope`` EXCEPT
    cache-state vars (``_CACHE_MARKERS``): two generators sharing one
    ``param_prefix`` (a float-pool and an int8-pool parity pair) share
    weight NAMES, so each needs its own scope — but copying cache vars
    would carry stale decode state across.  ``prefix`` restricts the
    copy to one model's ``param_prefix`` — required when ``src_scope``
    is shared with other models (their caches and params would
    otherwise be dragged along and re-uploaded for nothing).
    ``dst_prefix`` (requires ``prefix``) REWRITES the leading prefix on
    the way over — how a draft model under its own ``param_prefix`` is
    seeded from a target's weights (the ISSUE 15 draft==target parity
    pair, and the bench's shared-trunk draft construction).  Unset
    placeholders (``Scope.var()`` with no value) are skipped.  Returns
    the number of vars copied."""
    if dst_prefix is not None and prefix is None:
        raise ValueError("copy_weights: dst_prefix requires prefix")
    n = 0
    for name in list(src_scope.vars):
        if any(m in name for m in _CACHE_MARKERS):
            continue
        if prefix is not None and not name.startswith(prefix):
            continue
        val = src_scope.find_var(name)
        if val is None:
            continue
        out_name = name if dst_prefix is None \
            else dst_prefix + name[len(prefix):]
        dst_scope.set_var(out_name, np.array(np.asarray(val)))
        n += 1
    return n


def default_num_pages(src_len: int, max_out_len: int,
                      page_size: int) -> int:
    """The ctor's pool-sizing default: room for ~8 worst-case requests
    (+ the trash page)."""
    p_src = ceil_div(src_len, page_size)
    p_out = ceil_div(max_out_len, page_size)
    return 8 * (2 * p_src + p_out) + 1


# feeds of the chunked-prefill tower.  Their leading axis is the tower's
# WIDTH (one row per prefilling lane, padded to a member of
# ``tower_widths``); every other feed of the unified step keeps the
# lane count
TOWER_FEEDS = ("pf_word", "pf_pos", "pf_base", "pf_len", "enc_table",
               "enc_pages", "cross_pages", "w_offsets")


def tower_widths(n_slots: int) -> Tuple[int, ...]:
    """The closed set of prefill-tower widths at a lane count, derived
    and never configured: an eighth of the lanes, and the lanes.  The
    largest is always the lane count, so a burst that admits every lane
    at once still prefills them all in ONE step — no request ever waits
    for a tower row, and scheduling is what it was when the tower ran
    over every lane.  Each width is one executable (``aot_warm``
    resolves them all; ``bucket_set`` lists them)."""
    b = int(n_slots)
    return tuple(sorted({max(1, b // 8), b}))


def unified_bucket_set(program, n_slots: int):
    """The closed compile-signature set of a unified step program
    (``build_unified_program``, any ``verify_tokens``) at a lane count:
    one signature per tower width — the tower's feeds lead with the
    width, every other feed with the lane count (PR 10's
    ``enumerate_buckets``)."""
    from ..fluid.analysis.dataflow import ProgramView
    from ..fluid.analysis.recompile import enumerate_buckets

    view = ProgramView(program.desc)
    return [entry for w in tower_widths(n_slots)
            for entry in enumerate_buckets(
                view, batch_buckets=(int(n_slots),),
                leading=dict.fromkeys(TOWER_FEEDS, w))]


# mesh axes reserved for batch (data) sharding on the serving mesh —
# everything else is a tensor-parallel (model) axis
_BATCH_AXES = ("dp", "batch")


def model_axis_of(mesh_axes: Optional[Dict[str, int]]) -> Optional[str]:
    """The tensor-parallel axis of a ``{'batch': nb, 'model': nm}``
    serving mesh spec: the first non-batch axis with extent > 1, or
    None (pure data parallelism / single chip — the unsharded
    program)."""
    if not mesh_axes:
        return None
    for ax, n in mesh_axes.items():
        if ax not in _BATCH_AXES and int(n) > 1:
            return ax
    return None


def check_shardable(cfg: _Cfg, mesh_axes: Dict[str, int]) -> None:
    """Refuse mesh specs the head-sharded serving program cannot
    partition evenly: the pool's head axis, the fc column extents, and
    the MLP inner width must all divide the model-axis size (GSPMD
    would silently replicate a non-divisible dim, breaking the
    per-shard HBM plan the admission path budgets with)."""
    ax = model_axis_of(mesh_axes)
    if ax is None:
        return
    n = int(mesh_axes[ax])
    for what, extent in (("n_head", cfg.n_head),
                         ("d_inner_hid", cfg.d_inner_hid)):
        if extent % n:
            raise ValueError(
                f"mesh axis {ax}={n} cannot shard the model: {what}="
                f"{extent} is not divisible by {n}")


def build_unified_program(cfg: _Cfg, *, src_len: int, max_out_len: int,
                          page_size: int, num_pages: int, chunk_size: int,
                          param_prefix: str, kv_dtype: str = "float32",
                          verify_tokens: int = 1,
                          logit_masks: bool = False,
                          shard_axis: Optional[str] = None):
    """Build the unified prefill+decode program DESC — pure Python, no
    device allocation, no scope.  The generator's ``_build_unified``
    calls this with its own config; the gateway registry calls it with
    a manifest config to run the static peak-HBM planner BEFORE any
    construction (the pool/sidecar are persistable vars with recorded
    shapes, so the planner prices the full serving footprint from the
    desc alone).  Returns ``(prog, startup, next_ids, logits)``.

    ``verify_tokens=K`` (ISSUE 15) widens the decode half to a per-lane
    K-token axis: the chunked-prefill tower is unchanged, but the step
    feeds become ``trg_word``/``trg_pos``/``self_pages``/``self_offsets``
    [b, K] and the program scores all K positions causally in the one
    dispatch (``models.transformer.verify_step``) — the target side of
    speculative decoding, where K = draft length + 1.  A lane verifying
    fewer than K tokens (a plain non-speculative lane verifies exactly
    its current token) rides trash-page writes for the dead positions.
    ``logit_masks=True`` adds a ``logit_mask`` [b, K, vocab] additive
    float32 feed applied to the logits before the argmax — constrained
    generation with masks as DATA (a grammar change never recompiles).
    ``shard_axis`` (ISSUE 17) annotates the program for a tensor-
    parallel mesh axis of that name: the pool partitions on its minor
    (heads) axis, QKV/O and the MLP carry Megatron column/row shardings (the
    attention-output allreduce lands in-graph via GSPMD), the int8
    scale sidecar and all paging feeds stay replicated DATA, and the
    vocab head stays replicated for bitwise argmax parity.  The
    annotations are desc-level — the program still runs unsharded when
    no mesh is active.  The defaults build the exact PR 6 program,
    byte for byte."""
    c = cfg
    C = int(chunk_size)
    K = int(verify_tokens)
    p_src = ceil_div(int(src_len), int(page_size))
    p_out = ceil_div(int(max_out_len), int(page_size))
    pool_shape = [int(num_pages) * c.n_layer * 2, int(page_size),
                  c.n_head * c.d_key]
    scales_shape = [1, int(num_pages) * c.n_layer * 2, int(page_size)]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        block = prog.global_block()
        pool = block.create_var(name=f"{param_prefix}@kv_pool",
                                shape=pool_shape, dtype=kv_dtype,
                                persistable=True)
        if shard_axis:
            # [R, page_size, h*d] partitions on its minor axis, whose
            # equal slices are whole heads; the per-token row scatters
            # and the ragged attention walk are head-parallel, so every
            # shard pages its own slice of the pool against the SAME
            # replicated block tables
            pool.set_sharding((None, None, shard_axis))
        kv_scales = None
        if kv_dtype == "int8":
            # the sidecar stays replicated: one scale per (row, slot)
            # is the max over ALL heads, which GSPMD reduces with an
            # exact allreduce-max — int8 bytes stay bitwise identical
            # to the single-chip pool
            kv_scales = block.create_var(
                name=f"{param_prefix}@kv_scales", shape=scales_shape,
                dtype="float32", persistable=True)
        pf_word = layers.data("pf_word", [C], "int64")
        pf_pos = layers.data("pf_pos", [C], "int64")
        pf_base = layers.data("pf_base", [], "int32")
        pf_len = layers.data("pf_len", [], "int32")
        enc_table = layers.data("enc_table", [p_src], "int32")
        enc_pages = layers.data("enc_pages", [C], "int32")
        cross_pages = layers.data("cross_pages", [C], "int32")
        w_offsets = layers.data("w_offsets", [C], "int32")
        T.paged_prefill_chunk(
            pf_word, pf_pos, pf_base, pf_len, enc_table, enc_pages,
            cross_pages, w_offsets, pool, c.src_vocab_size,
            c.max_length, c.n_layer, c.n_head, c.d_key, c.d_value,
            c.d_model, c.d_inner_hid, param_prefix,
            kv_scales=kv_scales, mp_shard=shard_axis or False)
        trg_word = layers.data("trg_word", [K], "int64")
        trg_pos = layers.data("trg_pos", [K], "int64")
        self_table = layers.data("self_table", [p_out], "int32")
        self_pages = layers.data("self_pages", [K], "int32")
        self_offsets = layers.data("self_offsets", [K], "int32")
        self_lengths = layers.data("self_lengths", [], "int32")
        self_base = layers.data("self_base", [], "int32")
        cross_table = layers.data("cross_table", [p_src], "int32")
        src_lengths = layers.data("src_lengths", [], "int32")
        logit_mask = layers.data(
            "logit_mask", [K, c.trg_vocab_size], "float32") \
            if logit_masks else None
        logits = T.verify_step(
            trg_word, trg_pos, self_table, self_pages, self_offsets,
            self_lengths, self_base, cross_table, src_lengths, pool,
            c.trg_vocab_size, c.max_length, c.n_layer, c.n_head,
            c.d_key, c.d_value, c.d_model, c.d_inner_hid, param_prefix,
            kv_scales=kv_scales, n_tokens=K, logit_mask=logit_mask,
            mp_shard=shard_axis or False)
        next_ids = layers.argmax(logits, axis=-1)
    return prog, startup, next_ids, logits


# lanes assumed when pricing a generator's activations before any
# scheduler attaches (matches the default_num_pages ~8-request sizing)
HBM_ESTIMATE_LANES = 8


def estimate_generator_hbm(config: Dict, assume_lanes: int = None,
                           verify_tokens: int = 1,
                           logit_masks: bool = False,
                           mesh_axes: Optional[Dict[str, int]] = None):
    """Static peak-HBM plan for a paged generator described by a
    gateway manifest config — built and planned as a DESC, before any
    device allocation.  Params, the KV pool, and the int8 scale sidecar
    are persistable vars with recorded shapes; activations price at
    ``assume_lanes`` in-flight lanes.
    ``verify_tokens``/``logit_masks`` (ISSUE 15) price the speculative
    VERIFY shape of the program — K-token activations and the
    [lanes, K, vocab] mask feed are real peak-HBM contributors the
    admission budget must cover.  ``mesh_axes`` (ISSUE 17, also read
    from ``config["mesh_axes"]``) prices the PER-SHARD footprint of
    the sharded program: the pool and the column/row-sharded params
    scale by the model-axis extent while paging state and activations
    stay charged replicated.  Returns the
    ``analysis.cost.ProgramMemoryPlan``."""
    from ..fluid.analysis.cost import plan_program

    prog, mesh_axes = build_manifest_program(
        config, verify_tokens=verify_tokens, logit_masks=logit_masks,
        mesh_axes=mesh_axes)
    lanes = HBM_ESTIMATE_LANES if assume_lanes is None \
        else int(assume_lanes)
    return plan_program(prog, assume_batch=lanes, mesh_axes=mesh_axes)


def build_manifest_program(config: Dict, verify_tokens: int = 1,
                           logit_masks: bool = False,
                           mesh_axes: Optional[Dict[str, int]] = None):
    """Build the unified decode-step desc a gateway manifest describes —
    the shared front half of ``estimate_generator_hbm`` and the
    registry's sharding preflight.  ``mesh_axes`` defaults to
    ``config["mesh_axes"]``; params get their column/row annotations
    when a model axis is present.  Returns ``(program, mesh_axes)``."""
    cfg = _Cfg(int(config["src_vocab_size"]),
               int(config["trg_vocab_size"]),
               int(config.get("n_layer", 6)),
               int(config.get("n_head", 8)),
               int(config.get("d_key", 64)),
               int(config.get("d_value", 64)),
               int(config.get("d_model", 512)),
               int(config.get("d_inner_hid", 2048)),
               int(config.get("max_length", 256)))
    src_len = int(config.get("src_len", 64))
    max_out_len = int(config.get("max_out_len", 64))
    page_size = int(config.get("page_size", 8))
    num_pages = config.get("num_pages")
    if num_pages is None:
        num_pages = default_num_pages(src_len, max_out_len, page_size)
    if mesh_axes is None:
        mesh_axes = config.get("mesh_axes")
    shard_axis = model_axis_of(mesh_axes)
    if shard_axis is not None:
        check_shardable(cfg, mesh_axes)
    prog, _, _, _ = build_unified_program(
        cfg, src_len=src_len, max_out_len=max_out_len,
        page_size=page_size, num_pages=int(num_pages),
        chunk_size=int(config.get("chunk_size", 8)),
        param_prefix=str(config.get("param_prefix", "tf")),
        kv_dtype=str(config.get("kv_dtype", "float32")),
        verify_tokens=int(verify_tokens), logit_masks=bool(logit_masks),
        shard_axis=shard_axis)
    return prog, mesh_axes


class _Lane:
    """Host bookkeeping for one in-flight slot."""

    __slots__ = ("phase", "src", "s_true", "max_new", "enc_done",
                 "pending_chunk", "enc_table", "cross_table", "self_table",
                 "hashes", "hit_hashes", "inserted_hashes", "enc_owned",
                 "cross_owned", "cur", "pos", "rid")

    def __init__(self):
        self.reset()

    def reset(self):
        self.phase = "idle"        # idle | prefill | decode | hold
        self.src = None
        self.s_true = 0
        self.max_new = 0
        self.enc_done = 0
        self.pending_chunk = 0
        self.enc_table: List[int] = []
        self.cross_table: List[int] = []
        self.self_table: List[int] = []
        self.hashes: List[str] = []
        self.hit_hashes: List[str] = []
        self.inserted_hashes: List[str] = []
        self.enc_owned: List[int] = []
        self.cross_owned: List[int] = []
        self.cur = 0
        self.pos = 0
        self.rid = None             # the scheduler's request id (tag_slot)


class PagedTransformerGenerator:
    """Serving-side Transformer decoder over a paged KV pool.

    Same parameter-sharing contract as ``TransformerGenerator`` (explicit
    names under ``param_prefix``); the scheduler surface is page-aware:
    ``open_slots / admit_slot / clear_slot / lane_step`` plus
    ``can_admit / prompt_infeasible / pages_needed`` for admission
    control.  ``greedy`` / ``beam`` mirror the dense front-ends for
    parity testing and benchmarking."""

    page_aware = True

    def __init__(self, src_vocab_size, trg_vocab_size, *, n_layer=6,
                 n_head=8, d_key=64, d_value=64, d_model=512,
                 d_inner_hid=2048, max_length=256, src_len=64,
                 max_out_len=64, scope=None, executor=None, place=None,
                 param_prefix="tf", start_id=0, end_id=1,
                 page_size=8, num_pages=None, chunk_size=8,
                 prefix_sharing=True, topk_size=None,
                 kv_dtype="float32", mesh=None, mesh_axes=None,
                 host_pages=0, session_store=None, xfer_width=4,
                 demote_watermark=0):
        if d_key != d_value:
            raise ValueError("paged KV pool requires d_key == d_value "
                             "(one pool row shape serves both)")
        if kv_dtype not in _KV_ITEMSIZE:
            raise ValueError(f"kv_dtype={kv_dtype!r}: pick one of "
                             f"{sorted(_KV_ITEMSIZE)}")
        self.cfg = _Cfg(src_vocab_size, trg_vocab_size, n_layer, n_head,
                        d_key, d_value, d_model, d_inner_hid, max_length)
        # tensor-parallel serving (ISSUE 17): a batch × model mesh —
        # pass either a built jax Mesh or an axes spec like
        # {'batch': 1, 'model': 2} (the manifest form; make_mesh builds
        # it over the attached devices).  With neither, the engine is
        # the exact single-chip PR 6 program.
        if mesh is not None and mesh_axes is None:
            mesh_axes = dict(mesh.shape)
        self.mesh_axes = ({ax: int(n) for ax, n in mesh_axes.items()}
                          if mesh_axes else None)
        self.shard_axis = model_axis_of(self.mesh_axes)
        if self.mesh_axes and any(int(n) > 1
                                  for n in self.mesh_axes.values()):
            check_shardable(self.cfg, self.mesh_axes)
            if mesh is None:
                from ..parallel.mesh import make_mesh

                mesh = make_mesh(self.mesh_axes)
        else:
            mesh = None
        self.mesh = mesh
        self.src_len = int(src_len)
        self.max_out_len = int(max_out_len)
        self.prefix = param_prefix
        self.start_id = int(start_id)
        self.end_id = int(end_id)
        self.page_size = int(page_size)
        self.chunk = int(chunk_size)
        self.prefix_sharing = bool(prefix_sharing)
        self.topk_size = topk_size
        self.p_src = ceil_div(self.src_len, self.page_size)
        self.p_out = ceil_div(self.max_out_len, self.page_size)
        if num_pages is None:
            # shared with estimate_generator_hbm: the registry's static
            # admission plan must price the pool the ctor allocates
            num_pages = default_num_pages(self.src_len, self.max_out_len,
                                          self.page_size)
        self.num_pages = int(num_pages)
        self.scope = scope or fluid.Scope()
        self.exe = executor or fluid.Executor(place or fluid.TPUPlace(0))
        self.kv_dtype = kv_dtype
        self._pool_name = f"{param_prefix}@kv_pool"
        self._scales_name = f"{param_prefix}@kv_scales"
        self._pool_shape = (self.num_pages * n_layer * 2, self.page_size,
                            n_head * d_key)
        self._scales_shape = (1, self.num_pages * n_layer * 2,
                              self.page_size)
        self.page_bytes = kv_page_bytes(n_layer, n_head, d_key,
                                        self.page_size, kv_dtype)
        # tiered KV (ISSUE 20): host_pages > 0 attaches a host-RAM
        # demotion tier behind the allocator; session_store enables
        # suspend/resume of whole lanes; both are opt-in (defaults keep
        # the exact pre-tier destroy-on-evict engine).
        self.host_pages = int(host_pages)
        self.sessions = session_store
        self.xfer_width = max(1, int(xfer_width))
        self.demote_watermark = int(demote_watermark)
        self.alloc = PageAllocator(self.num_pages, self.page_size,
                                   host_pages=self.host_pages)
        self._xfer_progs = None
        self._pending_suspends: Dict[str, Dict] = {}
        self._tier_stats = {"suspends": 0, "suspend_drops": 0,
                            "resumes": 0, "resume_misses": 0,
                            "prefetches": 0, "eager_demotes": 0}
        if self.host_pages > 0:
            self.alloc.set_pager(self._tier_download, self._tier_upload,
                                 page_bytes=self.page_bytes)
        self._lanes: List[_Lane] = []
        self._slots = 0
        self._widths: Tuple[int, ...] = ()
        self._tower_width = 0
        self._steps = 0
        self._rows_fed = self._rows_live = 0
        self._steps_by_width: Dict[int, int] = {}
        self._tracer = _obs_tracing.tracer()
        self._beam_steps: Dict[int, tuple] = {}
        self._decode_prog = None
        self._build_unified()
        self._reset_pool()

    # -- mesh dispatch -------------------------------------------------------
    def _mesh_ctx(self):
        """Every device dispatch of a sharded generator runs under its
        mesh: the executor keys executables on the mesh content and
        applies the program's sharding annotations as jit in_shardings
        (the pjit path — one compile per mesh shape, cached like any
        other executable)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..parallel.mesh import mesh_guard

        return mesh_guard(self.mesh)

    # -- device pool ---------------------------------------------------------
    def _reset_pool(self):
        sharding = None
        if self.mesh is not None:
            # lay the pool out sharded from birth: a pool sized for the
            # MESH (num_pages beyond one chip's HBM) must never
            # materialise single-device
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(
                self.mesh, PartitionSpec(None, None, self.shard_axis))
        zero_pool(self.scope, self._pool_name, self._pool_shape,
                  self.kv_dtype, sharding)
        if self.kv_dtype == "int8":
            zero_pool(self.scope, self._scales_name, self._scales_shape,
                      "float32")

    def _pool_var(self, block):
        return pool_variable(
            block, self._pool_name, self._pool_shape, self.kv_dtype,
            (None, None, self.shard_axis) if self.shard_axis else None)

    def _scales_var(self, block):
        """The int8 pool's fp32 block-scale sidecar (None for float
        pools): one scale per (physical row, slot), written by
        quantized_paged_cache_write at the same page indirection the
        int8 bytes land in."""
        if self.kv_dtype != "int8":
            return None
        return pool_variable(block, self._scales_name, self._scales_shape,
                             "float32")

    # -- program builders ----------------------------------------------------
    def _build_unified(self):
        """ONE program = one dispatch: the chunked-prefill tower (causal
        encoder chunk + cross-KV page writes) over the lanes that
        prefill AND the paged decode step over every lane.  Tower rows
        beyond the prefilling lanes, and lanes that do not decode, ride
        along with trash-page writes and length-1 masks — so any mix of
        admitting / prefilling / decoding lanes replays one of a few
        executables, one per tower width (``tower_widths``)."""
        self._unified = build_unified_program(
            self.cfg, src_len=self.src_len, max_out_len=self.max_out_len,
            page_size=self.page_size, num_pages=self.num_pages,
            chunk_size=self.chunk, param_prefix=self.prefix,
            kv_dtype=self.kv_dtype, shard_axis=self.shard_axis)

    def _build_beam_step(self, W: int):
        """Paged beam step: in-dispatch copy-on-write page copies, the
        paged decode tower, and the beam_search selection op.  NO cache
        reorder lives in the graph — the host reassigns page tables to
        the parents' (shared, refcounted) pages instead of the dense
        path's whole-cache batch_gather copy."""
        c = self.cfg
        K = self.topk_size or min(2 * W, c.trg_vocab_size)
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup), fluid.unique_name.guard():
            pool = self._pool_var(prog.global_block())
            kv_scales = self._scales_var(prog.global_block())
            pre_ids = layers.data("pre_ids", [W], "int64")
            pre_scores = layers.data("pre_scores", [W], "float32")
            tok = layers.data("trg_word", [1], "int64")       # [bW, 1]
            tp = layers.data("trg_pos", [1], "int64")
            cow_src = layers.data("cow_src", [], "int32")
            cow_dst = layers.data("cow_dst", [], "int32")
            self_table = layers.data("self_table", [self.p_out], "int32")
            self_pages = layers.data("self_pages", [1], "int32")
            self_offsets = layers.data("self_offsets", [1], "int32")
            self_lengths = layers.data("self_lengths", [], "int32")
            self_base = layers.data("self_base", [], "int32")
            cross_table = layers.data("cross_table", [self.p_src], "int32")
            src_lengths = layers.data("src_lengths", [], "int32")
            if kv_scales is not None:
                pool, kv_scales = layers.paged_page_copy(
                    pool, cow_src, cow_dst, n_layer=c.n_layer,
                    scales=kv_scales)
            else:
                pool = layers.paged_page_copy(pool, cow_src, cow_dst,
                                              n_layer=c.n_layer)
            logits = T.paged_decode_step(
                tok, tp, self_table, self_pages, self_offsets,
                self_lengths, self_base, cross_table, src_lengths, pool,
                c.trg_vocab_size, c.max_length, c.n_layer, c.n_head,
                c.d_key, c.d_value, c.d_model, c.d_inner_hid, self.prefix,
                kv_scales=kv_scales,
                mp_shard=self.shard_axis or False)
            probs = layers.softmax(
                layers.reshape(logits, [-1, W, c.trg_vocab_size]))
            topk_scores, topk_idx = layers.topk(probs, k=K)
            sel_ids, sel_scores, parent = layers.beam_search(
                pre_ids, pre_scores, topk_idx, topk_scores, W,
                end_id=self.end_id)
        self._beam_steps[W] = (prog, startup, sel_ids, sel_scores, parent)
        return self._beam_steps[W]

    def _build_backtrace(self):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup), fluid.unique_name.guard():
            ids = layers.data("ids", [1], "int64", lod_level=1)
            scores = layers.data("scores", [1], "float32", lod_level=1)
            parents = layers.data("parents", [1], "int32", lod_level=1)
            sent_ids, sent_scores = layers.beam_search_decode(
                ids, scores, parents, end_id=self.end_id)
        self._decode_prog = (prog, sent_ids, sent_scores)
        return self._decode_prog

    # -- parameter init ------------------------------------------------------
    def init_params(self, seed: Optional[int] = None) -> None:
        """Random-init every parameter (the unified program touches the
        full set: encoder, cross projections, decoder, both embeddings,
        vocab head)."""
        if seed is not None:
            self._unified[1].random_seed = seed
        with fluid.scope_guard(self.scope), self._mesh_ctx():
            self.exe.run(self._unified[1])

    # -- admission accounting ------------------------------------------------
    def _prompt_pages(self, n_tokens: int) -> int:
        return ceil_div(max(1, int(n_tokens)), self.page_size)

    def _self_pages(self, max_new: int) -> int:
        return ceil_div(int(max_new), self.page_size) if max_new else 0

    def _resolve_max_new(self, max_new: Optional[int]) -> int:
        """None -> the generator's cap; 0 stays 0 (beam reserves no self
        pages at admission — it allocates them incrementally per lane)."""
        if max_new is None:
            return self.max_out_len
        return min(int(max_new), self.max_out_len)

    def pages_needed(self, src_tokens, max_new: Optional[int] = None) -> int:
        """Pages an admission would allocate right now (prompt pages for
        chunks the prefix cache does not already hold, x2 for enc+cross,
        plus the reserved decode pages)."""
        src = np.asarray(src_tokens).reshape(-1)
        mn = self._resolve_max_new(max_new)
        hits = 0
        if self.prefix_sharing:
            # count=False: this is an admission PROBE (the scheduler polls
            # it every step for a blocked queue head) — it must not skew
            # the prefix_hit_rate that cache_stats()/bench report
            hits = len(self.alloc.lookup_chain(
                chunk_hashes(src, self.page_size), count=False))
        return (2 * (self._prompt_pages(len(src)) - hits)
                + self._self_pages(mn))

    def can_admit(self, src_tokens, max_new: Optional[int] = None) -> bool:
        return self.pages_needed(src_tokens, max_new) <= \
            self.alloc.available()

    def prompt_infeasible(self, src_tokens,
                          max_new: Optional[int] = None) -> bool:
        """True when the request could NEVER be admitted: its prompt +
        reserved decode pages exceed the whole pool even with every
        other page free (prefix hits are not assumed — they can be
        evicted before admission)."""
        src = np.asarray(src_tokens).reshape(-1)
        mn = self._resolve_max_new(max_new)
        return (2 * self._prompt_pages(len(src)) + self._self_pages(mn)
                > self.alloc.total_usable)

    # -- continuous-batching surface -----------------------------------------
    def open_slots(self, n_slots: int) -> None:
        if self._lanes:
            for slot in range(len(self._lanes)):
                self.clear_slot(slot)
        self._slots = int(n_slots)
        self._widths = tower_widths(self._slots)
        for width in self._widths:
            # every key before the first step: ``counters()`` is read
            # from other threads while the step loop counts
            self._steps_by_width.setdefault(width, 0)
        self._lanes = [_Lane() for _ in range(self._slots)]

    def admit_slot(self, slot: int, src_tokens_1d,
                   max_new: Optional[int] = None) -> int:
        """Allocate the lane's page tables (prefix-cache hits first) and
        queue it for chunked prefill.  NO device dispatch happens here —
        the prefill work rides subsequent ``lane_step`` dispatches,
        interleaved with every other lane's decode."""
        if not self._lanes:
            raise RuntimeError("open_slots() before admit_slot()")
        lane = self._lanes[slot]
        if lane.phase != "idle":
            raise RuntimeError(f"admit_slot: slot {slot} is busy")
        src = np.asarray(src_tokens_1d).reshape(-1).astype(np.int64)
        s_true = len(src)
        if s_true > self.src_len:
            raise ValueError(
                f"admit_slot: prompt length {s_true} exceeds the "
                f"generator's src_len {self.src_len}; raise src_len or "
                f"truncate explicitly at the call site")
        mn = self._resolve_max_new(max_new)
        if self.prompt_infeasible(src, mn):
            raise PoolCapacityError(
                f"request needs {2 * self._prompt_pages(s_true) + self._self_pages(mn)} "
                f"pages for its prompt + decode reservation alone, but the "
                f"pool only has {self.alloc.total_usable} usable pages")
        n_prompt = self._prompt_pages(s_true)
        hashes = chunk_hashes(src, self.page_size)
        hits = self.alloc.lookup_chain(hashes) if self.prefix_sharing \
            else []
        n_hit = len(hits)
        # ref the hit chunks BEFORE allocating: alloc() evicts LRU
        # refcount-0 chunks under pressure, and an un-reffed hit is
        # exactly such a chunk — referencing first pins it (and its
        # pages) so the allocation can never evict what we just counted
        for h, _enc, _cross in hits:
            self.alloc.ref_chunk(h)
        try:
            fresh = self.alloc.alloc(2 * (n_prompt - n_hit)
                                     + self._self_pages(mn))
        except PoolCapacityError:
            for h, _enc, _cross in hits:
                self.alloc.unref_chunk(h)
            raise
        n_own = n_prompt - n_hit
        lane.src = src
        lane.s_true = s_true
        lane.max_new = mn
        lane.hashes = hashes
        lane.hit_hashes = [h for h, _, _ in hits]
        lane.inserted_hashes = []
        lane.enc_table = [e for _, e, _ in hits] + fresh[:n_own]
        lane.cross_table = [x for _, _, x in hits] + fresh[n_own:2 * n_own]
        lane.self_table = fresh[2 * n_own:]
        lane.enc_owned = fresh[:n_own]
        lane.cross_owned = fresh[n_own:2 * n_own]
        lane.enc_done = n_hit * self.page_size
        lane.pending_chunk = 0
        lane.cur = self.start_id
        lane.pos = 0
        if lane.enc_done >= s_true:     # whole prompt served from cache
            lane.phase = "decode"
        else:
            lane.phase = "prefill"
        return s_true

    def tag_slot(self, slot: int, rid: int) -> None:
        """Name the request a lane serves, so that the lane's own trace
        instants (``lane/prefill_chunk``) join the request's timeline."""
        self._lanes[slot].rid = rid

    def clear_slot(self, slot: int) -> None:
        """Retire a lane: release every page reference immediately.
        Prefix-cached chunks drop to the evictable list (still hittable,
        reclaimed under pressure); everything else returns to the free
        list."""
        lane = self._lanes[slot]
        if lane.phase == "idle":
            return
        for h in lane.hit_hashes + lane.inserted_hashes:
            self.alloc.unref_chunk(h)
        for p in lane.enc_owned + lane.cross_owned:
            self.alloc.unref(p)
        for p in lane.self_table:
            self.alloc.unref(p)
        lane.reset()

    # -- tiered KV & sessions (ISSUE 20) -------------------------------------
    def _xfer(self):
        """Lazily build the d2h/h2d copy-program pair: ``download``
        gathers W whole logical pages into a dense slab the host
        fetches; ``upload`` scatters such a slab back (Out aliases
        Pool).  W (``xfer_width``) is FIXED and short transfers pad
        with the trash page, so each program compiles exactly once —
        tiering adds two executables and zero recompiles."""
        if self._xfer_progs is not None:
            return self._xfer_progs
        c = self.cfg
        W = self.xfer_width
        rows = W * 2 * c.n_layer
        down, d_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(down, d_start), \
                fluid.unique_name.guard():
            block = down.global_block()
            pool = self._pool_var(block)
            kv_scales = self._scales_var(block)
            pages = layers.data("xfer_pages", [W], "int32",
                                append_batch_size=False)
            if kv_scales is not None:
                slab, sslab = layers.paged_page_gather(
                    pool, pages, n_layer=c.n_layer, scales=kv_scales)
                d_fetch = [slab, sslab]
            else:
                slab = layers.paged_page_gather(pool, pages,
                                                n_layer=c.n_layer)
                d_fetch = [slab]
        up, u_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(up, u_start), fluid.unique_name.guard():
            block = up.global_block()
            pool = self._pool_var(block)
            kv_scales = self._scales_var(block)
            pages = layers.data("xfer_pages", [W], "int32",
                                append_batch_size=False)
            data = layers.data("xfer_data", [rows, *self._pool_shape[1:]],
                               self.kv_dtype, append_batch_size=False)
            if kv_scales is not None:
                sdata = layers.data("xfer_scales",
                                    [1, rows, self.page_size], "float32",
                                    append_batch_size=False)
                layers.paged_page_scatter(pool, data, pages,
                                          n_layer=c.n_layer,
                                          scales=kv_scales,
                                          scale_data=sdata)
            else:
                layers.paged_page_scatter(pool, data, pages,
                                          n_layer=c.n_layer)
        self._xfer_progs = {"down": (down, d_fetch), "up": up}
        return self._xfer_progs

    def _tier_download(self, pages) -> Dict[str, object]:
        """Device->host: pull whole logical pages as host numpy.  Groups
        of ``xfer_width`` ride one fixed-signature dispatch each.
        Returns ``{"kv": [n*2L, ps, h*d], "scales": [1, n*2L, ps]|None}``
        with rows in the order of ``pages``."""
        progs = self._xfer()
        down, fetches = progs["down"]
        c = self.cfg
        W, L2, ps = self.xfer_width, 2 * c.n_layer, self.page_size
        page = self._pool_shape[1:]                 # (page_size, h*d)
        kv_parts: List[np.ndarray] = []
        sc_parts: List[np.ndarray] = []
        pages = [int(p) for p in pages]
        for i in range(0, len(pages), W):
            grp = pages[i:i + W]
            pad = np.full(W, TRASH_PAGE, np.int32)
            pad[:len(grp)] = grp
            with fluid.scope_guard(self.scope), self._mesh_ctx():
                out = self.exe.run(down, feed={"xfer_pages": pad},
                                   fetch_list=fetches, mode="infer")
            slab = np.asarray(out[0]).reshape(W * L2, *page)
            kv_parts.append(slab[:len(grp) * L2])
            if len(fetches) > 1:
                ssl = np.asarray(out[1]).reshape(1, W * L2, ps)
                sc_parts.append(ssl[:, :len(grp) * L2])
        kv = np.concatenate(kv_parts, axis=0) if kv_parts else \
            np.zeros((0, *page), self.kv_dtype)
        scales = np.concatenate(sc_parts, axis=1) if sc_parts else None
        return {"kv": kv, "scales": scales}

    def _tier_upload(self, pages, payload) -> None:
        """Host->device: scatter a ``_tier_download`` payload back into
        freshly allocated pages (same fixed-width program discipline;
        pad rows land on the trash page)."""
        progs = self._xfer()
        up = progs["up"]
        c = self.cfg
        W, L2, ps = self.xfer_width, 2 * c.n_layer, self.page_size
        kv = np.asarray(payload["kv"])
        scales = payload.get("scales")
        pages = [int(p) for p in pages]
        if kv.shape[0] != len(pages) * L2:
            raise ValueError(
                f"tier upload: payload holds {kv.shape[0] // L2} pages, "
                f"target list has {len(pages)}")
        for i in range(0, len(pages), W):
            grp = pages[i:i + W]
            pad = np.full(W, TRASH_PAGE, np.int32)
            pad[:len(grp)] = grp
            data = np.zeros((W * L2, *self._pool_shape[1:]), kv.dtype)
            data[:len(grp) * L2] = kv[i * L2:(i + len(grp)) * L2]
            feed = {"xfer_pages": pad, "xfer_data": data}
            if self.kv_dtype == "int8":
                sdata = np.zeros((1, W * L2, ps), np.float32)
                if scales is not None:
                    sdata[:, :len(grp) * L2] = \
                        np.asarray(scales)[:, i * L2:(i + len(grp)) * L2]
                feed["xfer_scales"] = sdata
            with fluid.scope_guard(self.scope), self._mesh_ctx():
                self.exe.run(up, feed=feed, fetch_list=[], mode="infer")

    def session_fingerprint(self) -> str:
        """The artifact key prefix a suspended lane's KV is only valid
        under: model geometry + pool dtype/layout + weights identity
        (the param prefix — two models sharing a scope differ here).
        A changed fingerprint turns every stored session into a clean
        miss (degrade to re-prefill), never a wrong-KV resume."""
        c = self.cfg
        doc = json.dumps([c.src_vocab_size, c.trg_vocab_size, c.n_layer,
                          c.n_head, c.d_key, c.d_value, c.d_model,
                          c.d_inner_hid, c.max_length, self.kv_dtype,
                          self.page_size, self.src_len, self.max_out_len,
                          self.prefix], separators=(",", ":"))
        return hashlib.sha256(doc.encode("utf-8")).hexdigest()[:24]

    def detach_slot(self, slot: int, session_id: str) -> bool:
        """Suspend a lane WITHOUT device work: the lane's page
        references (self pages, cross pages, chunk refs) transfer to a
        pending-suspend record and the slot frees immediately — safe to
        call under the scheduler lock at retire time.  The d2h copy and
        artifact store happen later in ``tier_maintenance`` (off the
        lock).  False when sessions are off or the lane is not in a
        suspendable phase (the caller falls back to ``clear_slot``)."""
        if self.sessions is None:
            return False
        lane = self._lanes[slot]
        if lane.phase not in ("decode", "hold") or not lane.self_table:
            return False
        old = self._pending_suspends.pop(session_id, None)
        if old is not None:
            # same session suspended twice before maintenance ran: the
            # newer lane state supersedes — drop the stale record's refs
            self._release_suspend_refs(old)
        self._pending_suspends[session_id] = {
            "src": np.array(lane.src), "s_true": lane.s_true,
            "max_new": lane.max_new, "pos": lane.pos, "cur": lane.cur,
            "self_table": list(lane.self_table),
            "cross_table": list(lane.cross_table),
            "cross_owned": list(lane.cross_owned),
            "hit_hashes": list(lane.hit_hashes),
            "inserted_hashes": list(lane.inserted_hashes),
            # a fully-cached admit reaches decode without _finish_prefill
            # — it still holds enc-owned refs that must release with the
            # record, not leak
            "enc_owned": list(lane.enc_owned),
        }
        lane.reset()
        return True

    def _release_suspend_refs(self, rec: Dict) -> None:
        for h in rec["hit_hashes"] + rec["inserted_hashes"]:
            self.alloc.unref_chunk(h)
        for p in rec["cross_owned"] + rec["enc_owned"]:
            self.alloc.unref(p)
        for p in rec["self_table"]:
            self.alloc.unref(p)

    def _complete_suspend(self, session_id: str) -> bool:
        """Finish one pending suspend: download the lane's used self
        pages + cross pages, store the checksummed artifact, release the
        page references.  Runs on the serve-loop thread OUTSIDE the
        scheduler lock (the PR 12 discipline — this is device + disk
        I/O).  The references are released even when the store fails:
        the session degrades to re-prefill, the pool never leaks."""
        rec = self._pending_suspends.pop(session_id, None)
        if rec is None:
            return False
        ps = self.page_size
        n_self_used = ceil_div(rec["pos"], ps) if rec["pos"] else 0
        ok = False
        try:
            cross = self._tier_download(rec["cross_table"])
            own = self._tier_download(rec["self_table"][:n_self_used]) \
                if n_self_used else {"kv": None, "scales": None}
            arrays = {"cross_kv": cross["kv"]}
            if cross["scales"] is not None:
                arrays["cross_scales"] = cross["scales"]
            if own["kv"] is not None:
                arrays["self_kv"] = own["kv"]
                if own["scales"] is not None:
                    arrays["self_scales"] = own["scales"]
            meta = {"pos": rec["pos"], "cur": rec["cur"],
                    "s_true": rec["s_true"], "max_new": rec["max_new"],
                    "src": [int(t) for t in rec["src"]],
                    "n_cross": len(rec["cross_table"]),
                    "n_self": n_self_used}
            ok = self.sessions.put(session_id, self.session_fingerprint(),
                                   meta, arrays)
        except Exception:
            ok = False
        finally:
            self._release_suspend_refs(rec)
        self._tier_stats["suspends" if ok else "suspend_drops"] += 1
        self._tracer.instant("session/suspend", cat="serving",
                             sid=session_id, ok=ok,
                             pages=len(rec["cross_table"]) + n_self_used)
        return ok

    def resume_slot(self, slot: int, session_id: str,
                    max_new: Optional[int] = None):
        """Resume a suspended session into an idle slot: allocate fresh
        cross + self pages, upload the artifact's KV (+ int8 scale
        sidecars), and restore the lane straight to ``decode`` phase at
        its recorded position — no re-prefill.  Runs OUTSIDE the
        scheduler lock (device + disk I/O, like ``admit_slot``).

        Returns ``{"s_true", "pos", "max_new"}`` on success or None on
        any miss — unknown/corrupt/stale artifact, position at the
        generator's cap, or pool pressure — in which case the caller
        degrades to a fresh ``admit_slot`` of the recorded prompt
        (greedy decode is deterministic, so degrading costs prefill
        latency, never wrong tokens)."""
        if self.sessions is None:
            return None
        if not self._lanes:
            raise RuntimeError("open_slots() before resume_slot()")
        lane = self._lanes[slot]
        if lane.phase != "idle":
            raise RuntimeError(f"resume_slot: slot {slot} is busy")
        if session_id in self._pending_suspends:
            # resumed before maintenance flushed it: complete the spill
            # now so the resume reads a stored artifact (one code path)
            self._complete_suspend(session_id)
        got = self.sessions.get(session_id, self.session_fingerprint())
        if got is None:
            self._tier_stats["resume_misses"] += 1
            return None
        meta, arrays = got
        pos = int(meta["pos"])
        ps = self.page_size
        # the self_table feed width is fixed at p_out: a resumed lane
        # continues within the SAME compiled signature, so its total
        # output (recorded pos + continuation) caps at max_out_len
        mn = self._resolve_max_new(max_new)
        mn = min(mn, self.max_out_len - pos)
        if mn <= 0:
            self._tier_stats["resume_misses"] += 1
            return None
        n_cross = int(meta["n_cross"])
        n_self_used = int(meta["n_self"])
        n_self = min(self.p_out, max(n_self_used,
                                     ceil_div(pos + mn, ps)))
        try:
            pages = self.alloc.alloc(n_cross + n_self)
        except PoolCapacityError:
            self._tier_stats["resume_misses"] += 1
            return None
        cross_pages = pages[:n_cross]
        self_pages = pages[n_cross:]
        try:
            self._tier_upload(cross_pages,
                              {"kv": arrays["cross_kv"],
                               "scales": arrays.get("cross_scales")})
            if n_self_used:
                self._tier_upload(self_pages[:n_self_used],
                                  {"kv": arrays["self_kv"],
                                   "scales": arrays.get("self_scales")})
        except Exception:
            for p in pages:
                self.alloc.unref(p)
            self._tier_stats["resume_misses"] += 1
            return None
        lane.src = np.asarray(meta["src"], np.int64)
        lane.s_true = int(meta["s_true"])
        lane.max_new = mn
        lane.hashes = []
        lane.hit_hashes = []
        lane.inserted_hashes = []
        lane.enc_table = []
        lane.enc_owned = []
        lane.cross_table = cross_pages
        lane.cross_owned = cross_pages
        lane.self_table = self_pages
        lane.enc_done = lane.s_true
        lane.pending_chunk = 0
        lane.cur = int(meta["cur"])
        lane.pos = pos
        lane.phase = "decode"
        self._tier_stats["resumes"] += 1
        self._tracer.instant("session/resume", cat="serving",
                             sid=session_id, slot=slot, pos=pos,
                             pages=len(pages))
        return {"s_true": lane.s_true, "pos": pos, "max_new": mn}

    def tier_maintenance(self, prefetch=None) -> bool:
        """The serve loop's off-lock tier slice: complete pending
        suspends (d2h + artifact store), prefetch-promote a queued
        prompt's demoted chunks during the admission gap, and eager-
        demote LRU chunks down to the free-page watermark.  Returns
        True when any device/disk work happened (the scheduler counts
        that as progress so shutdown drains suspends)."""
        did = False
        for sid in list(self._pending_suspends):
            self._complete_suspend(sid)
            did = True
        if prefetch is not None and self.prefix_sharing \
                and self.alloc.tiered:
            hashes = chunk_hashes(np.asarray(prefetch).reshape(-1),
                                  self.page_size)
            resident = len(self.alloc.lookup_chain(hashes, count=False))
            for h in hashes[resident:]:
                if not self.alloc.promote_chunk(h):
                    break
                self._tier_stats["prefetches"] += 1
                did = True
        if self.demote_watermark and self.alloc.tiered:
            while self.alloc.free_count() < self.demote_watermark:
                if not self.alloc.demote_one():
                    break
                self._tier_stats["eager_demotes"] += 1
                did = True
        if self.sessions is not None \
                and self.sessions.idle_spill_s is not None:
            # suspend-on-idle at the host-RAM level: sessions nobody
            # resumed lately drop their RAM copy (disk keeps them)
            if self.sessions.spill_idle():
                did = True
        return did

    def _finish_prefill(self, lane: _Lane) -> None:
        lane.phase = "decode"
        if self.prefix_sharing:
            full = lane.s_true // self.page_size
            for i in range(len(lane.hit_hashes), full):
                enc, cross = lane.enc_table[i], lane.cross_table[i]
                if self.alloc.insert_chunk(lane.hashes[i], enc, cross):
                    # ownership of BOTH pages transfers to the cache
                    # entry (released when the chunk is evicted)
                    lane.inserted_hashes.append(lane.hashes[i])
                    lane.enc_owned.remove(enc)
                    lane.cross_owned.remove(cross)
        # decode only reads CROSS pages: the lane's non-cached encoder-KV
        # pages (always at least the partial tail) are dead weight from
        # here on — free them now so admission capacity tracks what a
        # decoding request really holds (the dense baseline keeps no
        # encoder K/V either)
        for p in lane.enc_owned:
            self.alloc.unref(p)
        lane.enc_owned = []
        lane.enc_table = []

    def _prefill_arrays(self, width: Optional[int] = None
                        ) -> Dict[str, np.ndarray]:
        """The chunked-prefill half of a unified-program feed: one ROW
        per lane in phase ``prefill``, in slot order (recording each
        lane's ``pending_chunk``), padded to the smallest member of
        ``tower_widths`` that holds them; the padding rows ride
        trash-page writes.  The tower's feeds (``TOWER_FEEDS``) carry
        that width as their leading axis while the decode half keeps
        the lane count, so a step pays for the lanes that prefill and
        not for every lane.  ``width`` forces a (large enough) width:
        warm-up resolves each one with it, and the lowering-only
        reports price the widest.  Pair with ``_absorb_prefill()``
        AFTER the dispatch ran — the split lets the speculative
        generator (ISSUE 15) drive the same prefill machinery through
        its own verify/draft programs."""
        C, ps = self.chunk, self.page_size
        slots = [slot for slot, lane in enumerate(self._lanes)
                 if lane.phase == "prefill"]
        if width is None:
            P = next(w for w in self._widths if w >= len(slots))
        elif len(slots) <= int(width):
            P = int(width)
        else:
            raise ValueError(f"tower width {width} cannot hold "
                             f"{len(slots)} prefilling lanes")
        self._tower_width = P
        feed = {"pf_word": np.zeros((P, C), np.int64),
                "pf_pos": np.zeros((P, C), np.int64),
                "pf_base": np.zeros(P, np.int32),
                "pf_len": np.ones(P, np.int32),
                "enc_table": np.zeros((P, self.p_src), np.int32),
                "enc_pages": np.full((P, C), TRASH_PAGE, np.int32),
                "cross_pages": np.full((P, C), TRASH_PAGE, np.int32),
                "w_offsets": np.zeros((P, C), np.int32)}
        for row, slot in enumerate(slots):
            lane = self._lanes[slot]
            done = lane.enc_done
            m = min(C, lane.s_true - done)
            lane.pending_chunk = m
            feed["pf_word"][row, :m] = lane.src[done:done + m]
            feed["pf_pos"][row, :m] = np.arange(done, done + m)
            feed["pf_base"][row] = done
            feed["pf_len"][row] = done + m
            feed["enc_table"][row, :len(lane.enc_table)] = lane.enc_table
            pos = done + np.arange(m)
            feed["enc_pages"][row, :m], feed["w_offsets"][row, :m] = \
                token_slots(lane.enc_table, pos, ps)
            feed["cross_pages"][row, :m], _ = \
                token_slots(lane.cross_table, pos, ps)
        return feed

    def _decode_arrays(self, n_tokens: int = 1) -> Dict[str, np.ndarray]:
        """Idle-default decode-half feed arrays at a per-lane token
        axis of ``n_tokens`` (1 = the plain decode step; the ISSUE 15
        verify program feeds k+1) — idle lanes ride trash-page writes,
        length-1 masks, position 0.  The single home for the decode
        feed scaffold: ``lane_step`` and the speculative generator's
        draft/verify dispatches all fill lanes into THESE arrays, so a
        feed-shape change cannot silently diverge between them."""
        B = self._slots
        return {"trg_word": np.zeros((B, n_tokens), np.int64),
                "trg_pos": np.zeros((B, n_tokens), np.int64),
                "self_table": np.zeros((B, self.p_out), np.int32),
                "self_pages": np.full((B, n_tokens), TRASH_PAGE,
                                      np.int32),
                "self_offsets": np.zeros((B, n_tokens), np.int32),
                "self_lengths": np.ones(B, np.int32),
                "self_base": np.zeros(B, np.int32),
                "cross_table": np.zeros((B, self.p_src), np.int32),
                "src_lengths": np.ones(B, np.int32)}

    def _fill_decode_lane(self, dec: Dict[str, np.ndarray], slot: int,
                          lane, tokens, base_pos: int) -> None:
        """Fill one lane's rows of a ``_decode_arrays`` feed:
        ``tokens`` embed at positions ``base_pos..base_pos+n-1`` and
        their K/V scatter into the lane's self pages at those slots.
        The single home for the lane->feed convention — ``lane_step``
        (1 token at ``lane.pos``), the speculative draft dispatch (1
        token at the draft's own depth) and the k+1-token verify
        dispatch all go through here, so the page/offset/length
        arithmetic cannot silently diverge between them."""
        ps = self.page_size
        n = len(tokens)
        t = int(base_pos)
        if t + n > len(lane.self_table) * ps:
            raise RuntimeError(
                f"slot {slot}: writing {n} token(s) at position {t} "
                f"runs past the reserved {len(lane.self_table)} "
                f"self pages")
        for j, tok in enumerate(tokens):
            dec["trg_word"][slot, j] = tok
            dec["trg_pos"][slot, j] = t + j
            dec["self_pages"][slot, j] = lane.self_table[(t + j) // ps]
            dec["self_offsets"][slot, j] = (t + j) % ps
        dec["self_table"][slot, :len(lane.self_table)] = lane.self_table
        dec["self_lengths"][slot] = t + n
        dec["self_base"][slot] = t
        dec["cross_table"][slot, :len(lane.cross_table)] = \
            lane.cross_table
        dec["src_lengths"][slot] = lane.s_true

    def _absorb_prefill(self) -> None:
        """Post-dispatch bookkeeping for ``_prefill_arrays``: count the
        step and the tower rows it fed, and advance each prefilling
        lane past its pending chunk (emitting the trace instant AFTER
        the dispatch returned — a chunk that never ran must not appear
        in the request timeline)."""
        P = self._tower_width
        self._steps += 1
        self._steps_by_width[P] = self._steps_by_width.get(P, 0) + 1
        self._rows_fed += P * self.chunk
        for slot, lane in enumerate(self._lanes):
            if lane.phase != "prefill":
                continue
            who = {} if lane.rid is None else {"rid": lane.rid}
            self._tracer.instant(
                "lane/prefill_chunk", cat="serving", slot=slot,
                tokens=lane.pending_chunk,
                done=lane.enc_done + lane.pending_chunk,
                total=lane.s_true, **who)
            self._rows_live += lane.pending_chunk
            lane.enc_done += lane.pending_chunk
            lane.pending_chunk = 0
            if lane.enc_done >= lane.s_true:
                self._finish_prefill(lane)

    def lane_step(self, tower_width: Optional[int] = None
                  ) -> Dict[int, int]:
        """ONE dispatch over every lane: prefill lanes advance one
        source chunk, decode lanes emit one token.  Returns
        {slot: token} for the lanes that decoded.  ``tower_width`` is
        warm-up's (``aot_warm``): serving leaves the width to the
        number of lanes that prefill."""
        B = self._slots
        if B == 0:
            raise RuntimeError("open_slots() before lane_step()")
        tr = self._tracer
        with tr.span("engine/feed_build", cat="serving"):
            feed = self._prefill_arrays(tower_width)
            dec = self._decode_arrays()
            decoding: List[int] = []
            for slot, lane in enumerate(self._lanes):
                if lane.phase == "decode" and lane.self_table:
                    self._fill_decode_lane(dec, slot, lane, [lane.cur],
                                           lane.pos)
                    decoding.append(slot)
            prog, _, next_ids, _logits = self._unified
            feed.update(dec)
        with tr.span("engine/dispatch", cat="serving"), \
                fluid.scope_guard(self.scope), self._mesh_ctx():
            nxt, = self.exe.run(prog, feed=feed, fetch_list=[next_ids],
                                return_numpy=False, mode="infer")
        with tr.span("engine/fetch", cat="serving"):
            # the host blocks here until the device has finished the step
            ids = np.asarray(nxt).reshape(B)
        with tr.span("engine/absorb", cat="serving"):
            self._absorb_prefill()
            emitted: Dict[int, int] = {}
            for slot, lane in enumerate(self._lanes):
                if slot in decoding:
                    tok = int(ids[slot])
                    lane.cur = tok
                    lane.pos += 1
                    emitted[slot] = tok
        return emitted

    # -- greedy --------------------------------------------------------------
    def greedy(self, src_tokens, src_lengths, max_new: Optional[int] = None,
               stop_at_end: bool = True) -> np.ndarray:
        """Paged greedy decode of a whole batch; token-for-token
        identical to ``TransformerGenerator.greedy`` run with
        causal-encoder feeds (tests assert it).  Internally this is just
        the serving loop: admit every row, then lane_step until done."""
        src_tokens = np.asarray(src_tokens)
        src_lengths = np.asarray(src_lengths, np.int32)
        b = src_tokens.shape[0]
        max_new = min(max_new or self.max_out_len, self.max_out_len)
        self.open_slots(b)
        for i in range(b):
            self.admit_slot(i, src_tokens[i, :src_lengths[i]],
                            max_new=max_new)
        out: List[List[int]] = [[] for _ in range(b)]
        target = max_new
        while True:
            for i, lane in enumerate(self._lanes):
                if lane.phase == "decode" and len(out[i]) >= target:
                    lane.phase = "hold"
            if all(lane.phase in ("hold", "idle") for lane in self._lanes):
                break
            for slot, tok in self.lane_step().items():
                out[slot].append(tok)
            if stop_at_end and target == max_new:
                # dense semantics: stop at the first step where every
                # lane has emitted end_id — i.e. columns = the latest
                # first-end index + 1 (lanes keep decoding up to there)
                firsts = [row.index(self.end_id) + 1
                          if self.end_id in row else None for row in out]
                if all(f is not None or len(out[i]) >= max_new
                       for i, f in enumerate(firsts)):
                    target = min(max_new,
                                 max(f if f is not None else max_new
                                     for f in firsts))
        for i in range(b):
            self.clear_slot(i)
        return np.asarray([row[:target] for row in out], np.int64)

    # -- beam ----------------------------------------------------------------
    def beam(self, src_tokens, src_lengths, beam_size: int,
             max_new: Optional[int] = None, return_trace: bool = False):
        """Paged beam decode: prompts chunk-prefill through the unified
        program, then b*W beam lanes decode over shared pages — a
        reorder reassigns page tables (refcounted) and only a shared,
        partially-written page is copied (copy-on-write), never the
        whole cache."""
        W = int(beam_size)
        ps = self.page_size
        src_tokens = np.asarray(src_tokens)
        src_lengths = np.asarray(src_lengths, np.int32)
        b = src_tokens.shape[0]
        bw = b * W
        max_new = min(max_new or self.max_out_len, self.max_out_len)
        self.open_slots(b)
        for i in range(b):
            self.admit_slot(i, src_tokens[i, :src_lengths[i]], max_new=0)
        while any(lane.phase == "prefill" for lane in self._lanes):
            self.lane_step()
        prog, _, sel_ids_v, sel_scores_v, parent_v = \
            self._beam_steps.get(W) or self._build_beam_step(W)

        lane_tables: List[List[int]] = [[] for _ in range(bw)]
        lane_cross = np.zeros((bw, self.p_src), np.int32)
        lane_srclen = np.repeat(src_lengths, W).astype(np.int32)
        for i in range(b):
            tbl = self._lanes[i].cross_table
            for w in range(W):
                lane_cross[i * W + w, :len(tbl)] = tbl
        pre_ids = np.full((b, W), self.start_id, np.int64)
        pre_scores = np.concatenate(
            [np.zeros((b, 1), np.float32),
             np.full((b, W - 1), -1e9, np.float32)], axis=1)
        ids_steps = [pre_ids]
        score_steps = [pre_scores]
        parent_steps = [np.zeros((b, W), np.int32)]
        try:
            with fluid.scope_guard(self.scope), self._mesh_ctx():
                for t in range(max_new):
                    off = t % ps
                    cow_src = np.full(bw, TRASH_PAGE, np.int32)
                    cow_dst = np.full(bw, TRASH_PAGE, np.int32)
                    for ln in range(bw):
                        tbl = lane_tables[ln]
                        if off == 0:
                            tbl.append(self.alloc.alloc(1)[0])
                        elif self.alloc.refcount(tbl[-1]) > 1:
                            new = self.alloc.alloc(1)[0]
                            cow_src[ln] = tbl[-1]
                            cow_dst[ln] = new
                            self.alloc.unref(tbl[-1])
                            self.alloc.note_cow()
                            tbl[-1] = new
                    self_table = np.zeros((bw, self.p_out), np.int32)
                    self_pages = np.zeros((bw, 1), np.int32)
                    for ln in range(bw):
                        tbl = lane_tables[ln]
                        self_table[ln, :len(tbl)] = tbl
                        self_pages[ln, 0] = tbl[t // ps]
                    feed = {
                        "pre_ids": pre_ids, "pre_scores": pre_scores,
                        "trg_word": pre_ids.reshape(bw, 1),
                        "trg_pos": np.full((bw, 1), t, np.int64),
                        "cow_src": cow_src, "cow_dst": cow_dst,
                        "self_table": self_table,
                        "self_pages": self_pages,
                        "self_offsets": np.full((bw, 1), off, np.int32),
                        "self_lengths": np.full(bw, t + 1, np.int32),
                        "self_base": np.full(bw, t, np.int32),
                        "cross_table": lane_cross,
                        "src_lengths": lane_srclen,
                    }
                    si, ss, pa = self.exe.run(
                        prog, feed=feed,
                        fetch_list=[sel_ids_v, sel_scores_v, parent_v],
                        mode="infer")
                    pre_ids = np.asarray(si).astype(np.int64)
                    pre_scores = np.asarray(ss).astype(np.float32)
                    parent = np.asarray(pa).astype(np.int32)
                    # table reorder: each selected hypothesis continues
                    # from its PARENT's pages — ref the new view of every
                    # lane first, then drop the old references
                    new_tables = []
                    for i in range(b):
                        for w in range(W):
                            src_tbl = lane_tables[i * W + int(parent[i, w])]
                            for p in src_tbl:
                                self.alloc.ref(p)
                            new_tables.append(list(src_tbl))
                    for tbl in lane_tables:
                        for p in tbl:
                            self.alloc.unref(p)
                    lane_tables = new_tables
                    ids_steps.append(pre_ids)
                    score_steps.append(pre_scores)
                    parent_steps.append(parent)
                    if (pre_ids == self.end_id).all():
                        break
        finally:
            for tbl in lane_tables:
                for p in tbl:
                    self.alloc.unref(p)
            for i in range(b):
                self.clear_slot(i)
        out_ids, out_scores = self._backtrace(ids_steps, score_steps,
                                              parent_steps)
        if return_trace:
            return out_ids, out_scores, (ids_steps, score_steps,
                                         parent_steps)
        return out_ids, out_scores

    def _backtrace(self, ids_steps, score_steps, parent_steps):
        prog, sent_ids, sent_scores = self._decode_prog or \
            self._build_backtrace()
        steps = len(ids_steps)
        lens = np.full(steps, 1, np.int32)
        feed = {"ids": SeqArray(np.stack(ids_steps), lens),
                "scores": SeqArray(np.stack(score_steps), lens),
                "parents": SeqArray(np.stack(parent_steps), lens)}
        with fluid.scope_guard(self.scope):
            out_ids, out_scores = self.exe.run(
                prog, feed=feed, fetch_list=[sent_ids, sent_scores],
                mode="infer")
        return out_ids, np.asarray(out_scores)

    # -- load-time warm-up ---------------------------------------------------
    def step_variants(self) -> List[int]:
        """The widths the prefill tower can take at the open lane count:
        one executable of the unified step each.  A load path that
        finds this resolves them all (``aot_warm``)."""
        return list(self._widths)

    def bucket_set(self, n_slots: int):
        """The unified program's closed compile-signature set at the
        given lane count: one signature per tower width (the static
        form of the zero-recompile guarantee)."""
        return unified_bucket_set(self._unified[0], n_slots)

    def aot_warm(self, n_slots: int) -> None:
        """Resolve the unified executable AT THE SERVING LANE COUNT, at
        every tower width, without admitting any request: one all-idle
        ``lane_step`` each — every row rides along with trash-page
        writes and length-1 masks, so no KV state or lane bookkeeping
        changes.  Each is a compile, or a load from JAX's compilation
        cache when an earlier process left the executable there.
        Lanes are left open at ``n_slots`` (the scheduler re-opens them
        at attach anyway)."""
        if any(lane.phase != "idle" for lane in self._lanes):
            raise RuntimeError(
                "aot_warm: lanes are busy — pre-resolution is for "
                "load/publish time, not mid-traffic")
        self.open_slots(int(n_slots))
        for width in self._widths:
            self.lane_step(tower_width=width)

    # -- accounting ----------------------------------------------------------
    def kv_bytes_per_slot_dense(self) -> int:
        """What ONE dense lane costs in the PR 5 decoder — the baseline
        the paged pool's bytes-in-use is compared against (shared
        formula: decoder.dense_kv_bytes_per_slot)."""
        return dense_kv_bytes_per_slot(self.cfg, self.src_len,
                                       self.max_out_len)

    def kv_bytes_per_token(self) -> int:
        """HBM bytes one cached token costs across every layer, K and V
        — ``page_bytes / page_size`` (int8 pools include their fp32
        block-scale sidecar, so the bf16->int8 ratio is the honest
        ~2x, not an idealised 2.0)."""
        return self.page_bytes // self.page_size

    def static_hbm_estimate(self, assume_lanes: int = None):
        """Static peak-HBM plan of the unified serving program (params
        + KV pool + int8 sidecar + per-dispatch activations at
        ``assume_lanes``) — the number the gateway registry budgets
        with and the scheduler surfaces per lane group (ISSUE 11:
        admission runs on the planner, not a byte-count heuristic)."""
        from ..fluid.analysis.cost import plan_program

        lanes = HBM_ESTIMATE_LANES if assume_lanes is None \
            else int(assume_lanes)
        mesh_key = None if self.mesh_axes is None \
            else tuple(sorted(self.mesh_axes.items()))
        key = ("_hbm_plan", lanes, mesh_key)
        cached = getattr(self, "_static_hbm_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        # per-shard plan: a sharded generator budgets what ONE device
        # holds (the admission criterion ISSUE 17 flips from "fits one
        # chip" to "fits one shard")
        plan = plan_program(self._unified[0], assume_batch=lanes,
                            mesh_axes=self.mesh_axes)
        self._static_hbm_cache = (key, plan)
        return plan

    def counters(self) -> Dict[str, object]:
        """What the step has done since load, for ``sched.stats()``
        ("engine") and the benchmark's per-layer readers: dispatches of
        the unified program, by the tower width each took; the rows the
        tower was fed (width x chunk a step) and how many of them were
        a request's tokens."""
        return {"steps": self._steps,
                "steps_by_width": dict(self._steps_by_width),
                "tower_rows_fed": self._rows_fed,
                "tower_rows_live": self._rows_live}

    def cache_stats(self) -> Dict[str, object]:
        """Page / prefix / HBM accounting next to the executor's
        executable-cache counters (the 0-recompile assertion surface).
        The ``hbm`` block carries ``kv_dtype`` + pool-bytes accounting —
        what the capacity-contest test ranks paged-int8 > paged-bf16 >
        dense with."""
        pages = self.alloc.stats()
        active = sum(1 for lane in self._lanes
                     if lane.phase not in ("idle",))
        in_use_bytes = self.page_bytes * pages["in_use"]
        return {
            "executable": self.exe.cache_stats()["executable"],
            "pages": pages,
            "steps": self._steps,
            "hbm": {
                "kv_dtype": self.kv_dtype,
                "page_bytes": self.page_bytes,
                "kv_bytes_per_token": self.kv_bytes_per_token(),
                "pool_bytes": self.page_bytes * self.num_pages,
                "bytes_in_use": in_use_bytes,
                "bytes_per_active_slot": (in_use_bytes // active)
                if active else 0,
                "dense_bytes_per_slot": self.kv_bytes_per_slot_dense(),
            },
            "shard": self.shard_plan(),
            "tiers": {
                "host_pages": pages.get("host_pages", 0),
                "host_pages_used": pages.get("host_pages_used", 0),
                "host_chunks": pages.get("host_chunks", 0),
                "demotes": pages.get("demotes", 0),
                "promotes": pages.get("promotes", 0),
                "host_evictions": pages.get("host_evictions", 0),
                "spilled_bytes": pages.get("spilled_bytes", 0),
                "fetched_bytes": pages.get("fetched_bytes", 0),
                "pending_suspends": len(self._pending_suspends),
                **self._tier_stats,
            },
            "sessions": self.sessions.stats()
            if self.sessions is not None else None,
        }

    def shard_plan(self) -> Dict[str, object]:
        """The mesh/sharding summary observability and admission share:
        mesh axes, model-shard count, and the pool bytes ONE shard
        holds (the head-axis partition divides the pool exactly; the
        int8 sidecar replicates, so it is charged in full per shard)."""
        n_shards = (self.mesh_axes or {}).get(self.shard_axis, 1) \
            if self.shard_axis else 1
        pool_bytes = self.page_bytes * self.num_pages
        if self.kv_dtype == "int8":
            # split pool data (head-sharded) from the replicated sidecar
            rows = 2 * self.cfg.n_layer * self.num_pages
            sidecar = rows * self.page_size * 4
            per_shard = (pool_bytes - sidecar) // n_shards + sidecar
        else:
            per_shard = pool_bytes // n_shards
        return {
            "mesh_axes": dict(self.mesh_axes) if self.mesh_axes else None,
            "shard_axis": self.shard_axis,
            "n_model_shards": int(n_shards),
            "pool_bytes_per_shard": int(per_shard),
        }

    def collective_report(self) -> Dict[str, object]:
        """Predicted vs MEASURED collective traffic of the unified
        serving step on this generator's mesh: the static estimator
        (analysis/comms.estimate_comms) prices the TP partial-sum
        all-reduces from desc shardings alone, and the executor lowers
        the SAME program under the mesh and tallies the partitioner's
        actual collective instructions from the optimized HLO
        (Executor.collective_analysis).  The pair is the bench's
        honesty gate for the comms estimator.  Unsharded generators
        report an empty measured block (no partitioner, no
        collectives).  Lowering only — no KV state changes."""
        from ..fluid.analysis.comms import estimate_comms

        prog, _, next_ids, _ = self._unified
        lanes = self._slots or 1
        pred = estimate_comms(
            prog, options={"mesh_axes": dict(self.mesh_axes or {}),
                           "assume_batch": lanes})
        out: Dict[str, object] = {
            "predicted": {
                "allreduce_count": len(pred.collectives),
                "allreduce_payload_bytes": float(sum(
                    c["payload_bytes"] for c in pred.collectives
                    if c["kind"].startswith("allreduce"))),
                "per_axis": {a: dict(d)
                             for a, d in pred.per_axis.items()},
            },
            "measured": {},
        }
        if self.mesh is None:
            return out
        with fluid.scope_guard(self.scope), self._mesh_ctx():
            out["measured"] = self.exe.collective_analysis(
                prog, feed=self._step_feed(), fetch_list=[next_ids],
                mode="infer")
        return out

    def _step_feed(self) -> Dict[str, np.ndarray]:
        """A full unified-step feed at the open lane count and the
        widest tower (the step's peak), for the lowering-only reports
        (call between requests: a prefilling lane's pending chunk is
        recorded by ``_prefill_arrays``)."""
        if not self._slots:
            raise RuntimeError("open_slots() before lowering the step")
        feed = self._prefill_arrays(self._slots)
        feed.update(self._decode_arrays())
        return feed

    def compiled_step_hlo(self) -> str:
        """Optimized HLO text of the unified serving step at the open
        lane count, under this generator's mesh
        (``Executor.compiled_hlo``) — what the device is really given
        per token: the ragged attention as a Mosaic ``tpu_custom_call``
        or as gathers, the pool aliased in place or copied.  Lowering
        only — no KV state changes."""
        prog, _, next_ids, _ = self._unified
        with fluid.scope_guard(self.scope), self._mesh_ctx():
            return self.exe.compiled_hlo(prog, feed=self._step_feed(),
                                         fetch_list=[next_ids],
                                         mode="infer")

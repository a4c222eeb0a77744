"""Checkpoint / inference-model IO.

Analog of python/paddle/v2/fluid/io.py (save_vars:66, save_params:129,
save_persistables:142, load_*:156-232, save_inference_model:297,
load_inference_model:370) and the C++ stream serialization in
operators/save_op.cc / load_op.cc (version + dims + dtype + lod + raw bytes).

Tensor wire format: a JSON header line {dtype, shape, lod} followed by raw
little-endian bytes (lengths bytes appended for SeqArray).  Combine files
stack entries with a manifest.  Device arrays are fetched through the PJRT
runtime (np.asarray) and restored with device_put on next use.

Durability (reference go/pserver/service.go:119-175 checkpoint semantics):
every file is written to a temp name then atomically `os.replace`d, and
carries a trailing CRC32 of the payload that load verifies — a torn or
corrupted write can never be mistaken for a checkpoint.
"""

from __future__ import annotations

import json
import os
import struct
import zlib
from typing import Dict, List, Optional

import numpy as np

from .core.lod import SeqArray
from .executor import Executor, Scope, global_scope
from .framework import (Parameter, Program, Variable, default_main_program,
                        default_startup_program)

__all__ = ["save_tensor", "load_tensor", "save_tensors", "load_tensors",
           "save_vars", "save_params", "save_persistables", "load_vars",
           "load_params", "load_persistables", "save_inference_model",
           "load_inference_model", "merge_inference_model",
           "get_inference_program", "device_put_persistables",
           "model_version_dir", "list_model_versions",
           "publish_model_version", "save_versioned_inference_model",
           "set_current_version", "current_model_version",
           "CheckpointCorrupt"]

_MAGIC = b"PDTPU\x01"      # legacy: no checksum
_MAGIC2 = b"PDTPU\x02"     # payload followed by crc32 trailer


class CheckpointCorrupt(Exception):
    """A tensor file failed its CRC32 check (torn/partial write)."""


def _fsync_dir(dirname: str) -> None:
    """Persist the rename itself: without fsyncing the directory entry a
    power loss can roll back os.replace after the caller saw success."""
    try:
        fd = os.open(dirname, os.O_RDONLY)
    except OSError:  # platforms/filesystems without dir fsync
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _atomic_write(path: str, payload) -> None:
    """tmp + fsync + os.replace + dir fsync — the pserver checkpoint
    recipe (service.go:119-175 writes .tmp then renames).  ``payload`` is
    bytes, or a list of bytes-like pieces written one after another."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            for piece in (payload if isinstance(payload, list)
                          else [payload]):
                f.write(piece)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _fsync_dir(d)


def _read_checked(path: str) -> bytes:
    """Read a tensor/combine file, verify magic + CRC; returns payload
    (the bytes after the magic, without the crc trailer)."""
    with open(path, "rb") as f:
        buf = f.read()
    return unframe_bytes(buf, path)


def _raw(array: np.ndarray):
    """The array's bytes as a flat view: no copy of a contiguous array."""
    return np.ascontiguousarray(array).reshape(-1).view(np.uint8).data


def _tensor_parts(value) -> List:
    """A tensor's payload as pieces (length-prefixed header, then the
    data's own bytes as views): ``_tensor_bytes`` joins them, and
    ``save_tensor`` writes them one after another, so a large tensor is
    never copied whole just to be written."""
    if isinstance(value, SeqArray):
        data = np.asarray(value.data)
        lengths = np.asarray(value.lengths, np.int32)
        header = {"dtype": data.dtype.name, "shape": list(data.shape),
                  "lod": True, "batch": int(lengths.shape[0])}
        hb = json.dumps(header).encode()
        return [struct.pack("<I", len(hb)) + hb, _raw(data), _raw(lengths)]
    data = np.asarray(value)
    header = {"dtype": data.dtype.name, "shape": list(data.shape),
              "lod": False}
    hb = json.dumps(header).encode()
    return [struct.pack("<I", len(hb)) + hb, _raw(data)]


def _tensor_bytes(value) -> bytes:
    return b"".join(_tensor_parts(value))


def _tensor_from(buf, offset: int = 0, copy: bool = True):
    """``copy=False`` returns arrays that are read-only views of ``buf``
    (bytes or a memoryview): for a reader that hands them straight on."""
    (hlen,) = struct.unpack_from("<I", buf, offset)
    offset += 4
    header = json.loads(bytes(buf[offset: offset + hlen]).decode())
    offset += hlen
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    dt = np.dtype(header["dtype"]) if header["dtype"] != "bfloat16" else \
        np.dtype(__import__("ml_dtypes").bfloat16)
    n = int(np.prod(header["shape"])) * dt.itemsize
    data = np.frombuffer(buf, dtype=dt, count=n // dt.itemsize,
                         offset=offset).reshape(header["shape"])
    if copy:
        data = data.copy()
    offset += n
    if header.get("lod"):
        ln = header["batch"] * 4
        lengths = np.frombuffer(buf[offset: offset + ln],
                                dtype=np.int32).copy()
        offset += ln
        return SeqArray(data, lengths), offset
    return data, offset


def frame_bytes(payload: bytes) -> bytes:
    """MAGIC2 + payload + crc32 trailer — THE checkpoint wire framing;
    every durable artifact (tensor files, v2 parameter tars, master
    snapshots) shares it."""
    crc = struct.pack("<I", zlib.crc32(payload) & 0xFFFFFFFF)
    return _MAGIC2 + payload + crc


def unframe_bytes(data: bytes, what: str = "<bytes>") -> bytes:
    """Inverse of frame_bytes; raises CheckpointCorrupt on bad magic or
    CRC (legacy MAGIC1 passes through unchecked)."""
    if data[: len(_MAGIC2)] == _MAGIC2:
        payload, trailer = data[len(_MAGIC2): -4], data[-4:]
        (want,) = struct.unpack("<I", trailer)
        got = zlib.crc32(payload) & 0xFFFFFFFF
        if got != want:
            raise CheckpointCorrupt(
                f"{what}: crc mismatch (file {want:#x}, computed {got:#x})")
        return payload
    if data[: len(_MAGIC)] == _MAGIC:
        return data[len(_MAGIC):]
    raise CheckpointCorrupt(f"bad tensor data {what} (unknown magic)")


def tensor_to_bytes(value) -> bytes:
    """One tensor/SeqArray as a framed byte string (the unit the v2
    parameter tar stores per entry)."""
    return frame_bytes(_tensor_bytes(value))


def tensor_from_bytes(data: bytes, what: str = "<bytes>"):
    value, _ = _tensor_from(unframe_bytes(data, what), 0)
    return value


def save_tensor(value, path: str) -> None:
    """``tensor_to_bytes(value)`` into ``path`` (tmp + fsync + rename),
    written piece by piece with the checksum kept as it goes: the same
    file, without three copies of the tensor on the way."""
    parts = [_MAGIC2] + _tensor_parts(value)
    crc = 0
    for part in parts[1:]:
        crc = zlib.crc32(part, crc)
    parts.append(struct.pack("<I", crc & 0xFFFFFFFF))
    _atomic_write(path, parts)


def load_tensor(path: str, copy: bool = True):
    """The tensor in ``path``, checksum verified.  ``copy=False`` returns
    a read-only array over the file's bytes as read (no second and third
    copy of a large tensor): for a loader that puts it on the device and
    drops it."""
    if not copy:
        with open(path, "rb") as f:
            buf = f.read()
        # unframing a memoryview slices it without copying
        return _tensor_from(unframe_bytes(memoryview(buf), path), 0,
                            copy=False)[0]
    value, _ = _tensor_from(_read_checked(path), 0)
    return value


def save_tensors(named: Dict[str, object], path: str) -> None:
    """Combine-file variant (save_combine_op.cc)."""
    names = sorted(named)
    manifest = json.dumps(names).encode()
    payload = struct.pack("<I", len(manifest)) + manifest + b"".join(
        _tensor_bytes(named[n]) for n in names)
    _atomic_write(path, frame_bytes(payload))


def load_tensors(path: str) -> Dict[str, object]:
    buf = _read_checked(path)
    off = 0
    (mlen,) = struct.unpack_from("<I", buf, off)
    off += 4
    names = json.loads(buf[off: off + mlen].decode())
    off += mlen
    out = {}
    for n in names:
        out[n], off = _tensor_from(buf, off)
    return out


# -- program-level save/load (reference io.py:66-232) -----------------------

def _default_predicate(var: Variable) -> bool:
    return var.persistable


def save_vars(executor: Executor, dirname: str,
              main_program: Optional[Program] = None, vars=None,
              predicate=None, scope: Optional[Scope] = None) -> None:
    program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in program.list_vars()
                if (predicate or _default_predicate)(v)]
    os.makedirs(dirname, exist_ok=True)
    for v in vars:
        val = scope.find_var(v.name)
        if val is None:
            continue
        save_tensor(val, os.path.join(dirname, v.name))


def save_params(executor, dirname, main_program=None, **kw):
    return save_vars(executor, dirname, main_program,
                     predicate=lambda v: isinstance(v, Parameter), **kw)


def save_persistables(executor, dirname, main_program=None, **kw):
    return save_vars(executor, dirname, main_program,
                     predicate=_default_predicate, **kw)


def load_vars(executor: Executor, dirname: str,
              main_program: Optional[Program] = None, vars=None,
              predicate=None, scope: Optional[Scope] = None) -> None:
    program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in program.list_vars()
                if (predicate or _default_predicate)(v)]
    for v in vars:
        path = os.path.join(dirname, v.name)
        if os.path.exists(path):
            scope.set_var(v.name, load_tensor(path))


def load_params(executor, dirname, main_program=None, **kw):
    return load_vars(executor, dirname, main_program,
                     predicate=lambda v: isinstance(v, Parameter), **kw)


def load_persistables(executor, dirname, main_program=None, **kw):
    return load_vars(executor, dirname, main_program,
                     predicate=_default_predicate, **kw)


# -- inference packaging (reference io.py:297,370) --------------------------

def prune_program(program: Program, targets: List[Variable]) -> Program:
    """Backward-slice the global block to the ops needed for `targets` —
    analog of the reference's Program.prune (framework.py:893 + prune.cc).
    The slice itself runs in the native IR library (csrc/ir.cc
    prune_block) when built, with the identical pure-Python walk as
    fallback (parity-tested in tests/test_native_ir.py)."""
    pruned = program.clone(for_test=True)
    block = pruned.global_block()
    needed = {t.name if isinstance(t, Variable) else str(t) for t in targets}

    # strip training-only ops BEFORE slicing — the reference does this via
    # OpRole flags in clone(for_test) (framework.py:893).  Without it, a
    # forward tower built AFTER optimizer.minimize() (e.g. a generation
    # tower sharing trained parameters) re-captures the whole training
    # graph: the reverse slice sees the optimizer update as "the writer" of
    # a needed parameter and chases grads all the way back to the labels.
    # Train-only ops are exactly those touching an @GRAD-suffixed var
    # (every grad op and every optimizer update reads one).  Skipped when
    # the caller explicitly targets a gradient (debug slices of @GRAD
    # vars must keep their producers).
    want_grads = any(n.endswith("@GRAD") for n in needed)

    def _touches_grad(od) -> bool:
        for ns in list(od.inputs.values()) + list(od.outputs.values()):
            for n in ns:
                if n and n.endswith("@GRAD"):
                    return True
        return False

    kept_descs = (block.desc.ops if want_grads else
                  [od for od in block.desc.ops if not _touches_grad(od)])
    if len(kept_descs) != len(block.desc.ops):
        kept = {id(od) for od in kept_descs}
        block.desc.ops = kept_descs
        block.ops = [op for op in block.ops if id(op.desc) in kept]
        pruned._bump_version()

    keep_idx = None
    from .. import native

    if native.available():
        try:
            keep_idx = native.prune(pruned, sorted(needed))
        except RuntimeError:
            keep_idx = None
    if keep_idx is None:
        # identical walk over the DESC ops (the native lib's view)
        keep_idx = []
        descs = block.desc.ops
        for i in range(len(descs) - 1, -1, -1):
            od = descs[i]
            outs = {n for ns in od.outputs.values() for n in ns}
            if outs & needed:
                keep_idx.append(i)
                needed |= {n for ns in od.inputs.values() for n in ns if n}
        keep_idx.reverse()
    # indices address desc.ops; wrappers are filtered by desc identity so
    # a desc-only op (no Python wrapper) cannot shift the alignment
    kept_descs = {id(block.desc.ops[i]) for i in keep_idx}
    block.desc.ops = [od for od in block.desc.ops if id(od) in kept_descs]
    block.ops = [op for op in block.ops if id(op.desc) in kept_descs]
    pruned._bump_version()
    return pruned


def save_inference_model(dirname: str, feeded_var_names: List[str],
                         target_vars: List[Variable], executor: Executor,
                         main_program: Optional[Program] = None,
                         scope: Optional[Scope] = None,
                         export_stablehlo_module: bool = False,
                         stablehlo_batch_size: int = 1,
                         stablehlo_seq_len: int = 32) -> None:
    """reference io.py:297: prune to the inference slice, record feed/fetch
    ops, persist program + params.  ``export_stablehlo_module=True``
    additionally writes model.stablehlo(.json) for the native PJRT
    serving tier (csrc/pjrt_runner.cc)."""
    program = main_program or default_main_program()
    pruned = prune_program(program, target_vars)
    block = pruned.global_block()
    for i, name in enumerate(feeded_var_names):
        block.desc.prepend_op(__import__(
            "paddle_tpu.fluid.core.desc", fromlist=["OpDesc"]).OpDesc(
            "feed", {"X": [name]}, {"Out": [name]}, {"col": i}))
    for i, v in enumerate(target_vars):
        block.desc.append_op(__import__(
            "paddle_tpu.fluid.core.desc", fromlist=["OpDesc"]).OpDesc(
            "fetch", {"X": [v.name]}, {"Out": [v.name]}, {"col": i}))
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "__model__"), "wb") as f:
        f.write(pruned.serialize_to_string())
    save_persistables(executor, dirname, program, scope=scope)
    if export_stablehlo_module:
        export_stablehlo(dirname, pruned, feeded_var_names,
                         [v.name for v in target_vars], scope=scope,
                         batch_size=stablehlo_batch_size,
                         seq_len=stablehlo_seq_len)


_MERGED_MAGIC = b"PTPUMRG1"


def merge_inference_model(dirname: str, out_path: str) -> None:
    """Pack a save_inference_model directory into ONE deployable file —
    the analog of the reference's merged-model tool
    (trainer/MergeModel.cpp: ModelConfig + parameters in one blob for
    capi embedding).  Container: magic, u64 entry count, then per entry
    [u32 name_len][name][u64 data_len][data]; entry bytes are the exact
    on-disk file bytes (tensor entries keep their CRC framing).  Served
    by the C engine via ``ptpu_create_for_inference_merged``."""
    import struct

    names = sorted(n for n in os.listdir(dirname)
                   if os.path.isfile(os.path.join(dirname, n))
                   and not n.startswith("model.stablehlo"))
    if "__model__" not in names:
        raise ValueError(f"{dirname} is not a save_inference_model "
                         f"directory (no __model__)")
    payload = [_MERGED_MAGIC, struct.pack("<Q", len(names))]
    for name in names:
        with open(os.path.join(dirname, name), "rb") as f:
            data = f.read()
        nb = name.encode()
        payload += [struct.pack("<I", len(nb)), nb,
                    struct.pack("<Q", len(data)), data]
    _atomic_write(out_path, b"".join(payload))


def load_inference_model(dirname: str, executor: Executor,
                         scope: Optional[Scope] = None,
                         to_device: bool = False):
    """reference io.py:370 -> (program, feed_names, fetch_targets).

    ``to_device=True`` uploads every loaded persistable to the device
    immediately (``jax.device_put``) instead of leaving host numpy in
    the scope — the serving path (serving/engine.py) wants the weights
    resident BEFORE the first request so no dispatch ever pays the H2D
    transfer."""
    with open(os.path.join(dirname, "__model__"), "rb") as f:
        program = Program.parse_from_string(f.read())
    block = program.global_block()
    feed_names = [op.input("X")[0] for op in block.desc.ops
                  if op.type == "feed"]
    fetch_names = [op.output("Out")[0] for op in block.desc.ops
                   if op.type == "fetch"]
    load_persistables(executor, dirname, program, scope=scope)
    if to_device:
        device_put_persistables(scope or global_scope(), program)
    fetch_vars = [block.vars[n] for n in fetch_names]
    return program, feed_names, fetch_vars


def device_put_persistables(scope: Scope,
                            program: Optional[Program] = None) -> int:
    """Upload every host-resident (numpy) value in ``scope`` to the
    device — restricted to ``program``'s persistables when one is given.
    THE single implementation behind ``load_inference_model(
    to_device=True)`` and ``serving.InferenceEngine.place_weights``;
    returns the number of arrays uploaded."""
    import jax

    if program is not None:
        names = [v.name for v in program.list_vars() if v.persistable]
    else:
        names = list(scope.vars)
    n = 0
    for name in names:
        val = scope.find_var(name)
        if isinstance(val, np.ndarray):
            scope.set_var(name, jax.device_put(val))
            n += 1
    return n


# -- versioned artifact layout (ISSUE 10: the gateway's model store) --------

# staging dirs end with this suffix so an unpublished (possibly torn)
# artifact can never be mistaken for a version by list_model_versions
# or ModelRegistry.load
_STAGING_SUFFIX = ".staging.tmp"

# on-disk deploy marker (ISSUE 12): the last PROMOTED version of a
# model, written by the release controller / lifecycle CLI so a process
# restart serves the last good version — not merely the newest artifact
# on disk (which may be an unvetted or rolled-back candidate)
CURRENT_MARKER = "CURRENT"


def model_version_dir(root: str, model_name: str, version: str) -> str:
    """``<root>/<model>/<version>/`` — one save_inference_model artifact
    (or generator artifact, see serving.gateway.ModelRegistry) per
    version, so hot-swap is "write the new version beside the old one,
    flip the alias"."""
    return os.path.join(root, str(model_name), str(version))


def list_model_versions(root: str, model_name: str) -> List[str]:
    """PUBLISHED versions on disk for ``model_name``, sorted (numeric
    versions numerically: v2 < v10).  Staging dirs of in-flight or
    crashed publishes (``*.staging.tmp``) are not versions and are
    skipped."""
    base = os.path.join(root, str(model_name))
    if not os.path.isdir(base):
        return []

    def key(v: str):
        digits = "".join(c for c in v if c.isdigit())
        return (int(digits) if digits else 0, v)

    return sorted((d for d in os.listdir(base)
                   if os.path.isdir(os.path.join(base, d))
                   and not d.endswith(".tmp")), key=key)


def set_current_version(root: str, model_name: str, version: str) -> None:
    """Atomically mark ``version`` as the deployed one (the release
    controller's promote/rollback durability point)."""
    _atomic_write(os.path.join(root, str(model_name), CURRENT_MARKER),
                  str(version).encode())


def current_model_version(root: str, model_name: str) -> Optional[str]:
    """The marked deployed version, or None when no marker exists or it
    points at a version no longer on disk (pruned — fall back to the
    caller's own default, e.g. newest)."""
    path = os.path.join(root, str(model_name), CURRENT_MARKER)
    try:
        with open(path, "r", encoding="utf-8") as f:
            version = f.read().strip()
    except OSError:
        return None
    if not version or not os.path.isdir(
            model_version_dir(root, model_name, version)):
        return None
    return version


def publish_model_version(root: str, model_name: str, version: str,
                          writer) -> str:
    """Crash-safe versioned-artifact publish — the CheckpointManager
    discipline applied to the model store: ``writer(staging_dir)``
    builds the artifact into an unpublished staging dir, every file is
    fsynced, then ONE atomic rename makes the version visible.  A crash
    at any point leaves either no version or the complete version —
    never a torn artifact for ``ModelRegistry.load`` to trip over.
    Stale staging dirs from crashed publishes are swept on the next
    publish of the same model.  Returns the published directory."""
    final = model_version_dir(root, model_name, version)
    base = os.path.dirname(final)
    os.makedirs(base, exist_ok=True)
    # GC staging leftovers of crashed publishes (any pid: a dead writer
    # never comes back for them — same rule as CheckpointManager._prune)
    for name in os.listdir(base):
        if name.endswith(_STAGING_SUFFIX):
            import shutil

            shutil.rmtree(os.path.join(base, name), ignore_errors=True)
    staging = f"{final}.{os.getpid()}{_STAGING_SUFFIX}"
    os.makedirs(staging)
    try:
        writer(staging)
        # fsync EVERY staged file before the rename can make it
        # reachable: save_inference_model's __model__ is a plain write,
        # and the publish must never outrun the bytes it names
        for name in os.listdir(staging):
            path = os.path.join(staging, name)
            if not os.path.isfile(path):
                continue
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        _fsync_dir(staging)
        # chaos point (ISSUE 12): a seeded "crash" after the artifact
        # is fully staged but BEFORE it is published — the torn-publish
        # regression tests inject here
        from ..resilience.chaos import injector

        injector().maybe_fail("io.publish")
        # re-publish of the same version: move the published artifact
        # ASIDE (a .tmp name the listing skips) rather than deleting it
        # first — deleting before the rename would let a crash in the
        # gap destroy the only copy of a possibly-serving version
        replaced = None
        if os.path.exists(final):
            replaced = f"{final}.{os.getpid()}.replaced{_STAGING_SUFFIX}"
            os.rename(final, replaced)
        os.rename(staging, final)          # atomic publish
        if replaced is not None:
            import shutil

            shutil.rmtree(replaced, ignore_errors=True)
    except BaseException:
        import shutil

        shutil.rmtree(staging, ignore_errors=True)
        raise
    _fsync_dir(base)
    return final


def save_versioned_inference_model(root: str, model_name: str,
                                   version: str,
                                   feeded_var_names: List[str],
                                   target_vars: List[Variable],
                                   executor: Executor,
                                   main_program: Optional[Program] = None,
                                   scope: Optional[Scope] = None,
                                   manifest: Optional[Dict] = None) -> str:
    """``save_inference_model`` into the versioned gateway layout via
    the crash-safe staged publish; returns the artifact directory.
    ``manifest`` (written as ``gateway.json``, the ModelRegistry
    manifest) rides inside the same atomic publish — e.g.
    ``{"kind": "engine", "config": {"quantize": "int8"}}`` for an int8
    PTQ candidate."""

    def writer(staging: str) -> None:
        save_inference_model(staging, feeded_var_names, target_vars,
                             executor, main_program=main_program,
                             scope=scope)
        if manifest is not None:
            with open(os.path.join(staging, "gateway.json"), "w",
                      encoding="utf-8") as f:
                json.dump(manifest, f, indent=1)

    return publish_model_version(root, model_name, version, writer)


def get_inference_program(target_vars, main_program=None):
    program = main_program or default_main_program()
    if not isinstance(target_vars, (list, tuple)):
        target_vars = [target_vars]
    return prune_program(program, target_vars)


def export_stablehlo(dirname: str, program, feed_names, fetch_names,
                     scope=None, batch_size: int = 1,
                     seq_len: int = 32) -> None:
    """Export the inference step as a StableHLO module + meta json — the
    artifact csrc/pjrt_runner.cc serves through any PJRT C-API plugin
    (TPU serving with no Python; reference inference/io.h:32 analog).

    Parameters are module ARGUMENTS (meta ``params`` lists them in
    positional order; the runner loads each from the CRC-framed tensor
    file ``dirname/<name>`` written by save_persistables and uploads it
    once at create time) — r3 baked them in as textual-MLIR constants,
    which capped the tier at toy-model sizes.  Feeds are dtype-tagged
    (int32/int64 word ids serve natively); a feed whose VarDesc carries a
    lod_level exports as TWO runner inputs, ``name`` (padded
    [batch, seq_len, ...] data) and ``name.lengths`` (int32 [batch]) —
    the dense-pair encoding of the reference capi's
    sequence_start_positions (capi/arguments.cpp).  SeqArray fetch
    targets likewise export as a (data, lengths) output pair.
    """
    import jax
    import numpy as np

    from .core.lod import SeqArray
    from .executor import Executor, HOST_OPS, global_scope
    from .lowering import MARKER_OPS, build_step_fn

    scope = scope or global_scope()
    desc = program.desc
    block = desc.global_block()
    feed_specs = []               # flat ShapeDtypeStructs, runner order
    metas = []
    lod_feeds = set()
    for name in feed_names:
        vd = block.vars[name]
        dtype = np.dtype(vd.dtype or "float32")
        if dtype not in (np.dtype(np.float32), np.dtype(np.int32),
                         np.dtype(np.int64)):
            raise ValueError(
                f"export_stablehlo: feed {name!r} has dtype {dtype}; the "
                f"native runner ABI serves float32/int32/int64 feeds")
        if not jax.config.jax_enable_x64:
            # the lowered module's real input types: jax canonicalizes
            # 64-bit dtypes away, and the meta must describe the ARTIFACT
            dtype = {np.dtype(np.int64): np.dtype(np.int32),
                     np.dtype(np.float64): np.dtype(np.float32)
                     }.get(dtype, dtype)
        shape = [int(d) for d in (vd.shape or []) if d not in (-1, None)]
        if (vd.lod_level or 0) > 0:
            lod_feeds.add(name)
            # vd.shape holds PER-STEP feature dims (batch/time are the
            # -1s filtered above): keep all of them after [batch, time]
            full = [batch_size, seq_len] + shape
            feed_specs.append(jax.ShapeDtypeStruct(tuple(full), dtype))
            feed_specs.append(jax.ShapeDtypeStruct((batch_size,), np.int32))
            metas.append({"name": name, "shape": full, "dtype": str(dtype),
                          "lod": True})
            metas.append({"name": f"{name}.lengths",
                          "shape": [batch_size], "dtype": "int32"})
        else:
            full = [batch_size if d in (-1, None) else int(d)
                    for d in (vd.shape or [])]
            feed_specs.append(jax.ShapeDtypeStruct(tuple(full), dtype))
            metas.append({"name": name, "shape": full, "dtype": str(dtype)})
    traced_ops = [op for op in block.ops
                  if op.type not in HOST_OPS and op.type not in MARKER_OPS]
    exe = Executor(None)
    state_in, _ = exe._classify_structure(traced_ops, set(feed_names),
                                          fetch_names, block)
    state_vals = exe._fetch_state(state_in, traced_ops, fetch_names, scope)
    # parameters ride as runtime arguments; the rare SeqArray state entry
    # (no dense tensor file format for the runner) stays a baked constant
    param_names = sorted(n for n, v in state_vals.items()
                         if not hasattr(v, "lengths"))
    state_const = {k: v for k, v in state_vals.items()
                   if k not in param_names}
    param_vals = {n: np.asarray(state_vals[n]) for n in param_names}
    param_metas = []
    for n in param_names:
        arr = param_vals[n]
        entry = {"name": n, "shape": [int(d) for d in arr.shape],
                 "dtype": str(arr.dtype)}
        if not jax.config.jax_enable_x64:
            # same artifact-vs-declared rule as feeds: the module's arg
            # type is the canonical 32-bit one; a 64-bit persistable gets
            # a converted side-file so the runner uploads what the
            # executable expects (the original checkpoint file untouched)
            canon = {np.dtype(np.int64): np.dtype(np.int32),
                     np.dtype(np.float64): np.dtype(np.float32)
                     }.get(arr.dtype)
            if canon is not None:
                arr = arr.astype(canon)
                entry["dtype"] = str(canon)
                entry["file"] = f"{n}.stablehlo-cast"
                save_tensor(arr, os.path.join(dirname, entry["file"]))
        param_metas.append(entry)
        path = os.path.join(dirname, n)
        if not os.path.exists(path):      # not persistable-saved: write it
            save_tensor(param_vals[n], path)
    step = build_step_fn(desc, 0, list(feed_names), state_in, [],
                         list(fetch_names), "infer")
    rng = np.zeros(2, np.int32)
    n_params = len(param_names)

    def infer_fn(*arrays):
        params = dict(zip(param_names, arrays[:n_params]))
        params.update(state_const)
        fd = {}
        i = n_params
        for name in feed_names:
            if name in lod_feeds:
                fd[name] = SeqArray(arrays[i], arrays[i + 1])
                i += 2
            else:
                fd[name] = arrays[i]
                i += 1
        fetches, _ = step(fd, params, rng)
        flat = []
        for f in fetches:
            if isinstance(f, SeqArray):
                flat.append(f.data)
                flat.append(jnp_asarray_i32(f.lengths))
            else:
                flat.append(f)
        return tuple(flat)

    def jnp_asarray_i32(x):
        import jax.numpy as jnp

        return jnp.asarray(x, jnp.int32)

    args = [jax.ShapeDtypeStruct(param_vals[n].shape, param_vals[n].dtype)
            for n in param_names] + feed_specs
    lowered = jax.jit(infer_fn).lower(*args)
    module_text = str(lowered.compiler_ir(dialect="stablehlo"))
    outs = jax.eval_shape(infer_fn, *args)
    out_metas = []
    for i, o in enumerate(outs):
        dt = np.dtype(o.dtype)
        if dt not in (np.dtype(np.float32), np.dtype(np.int32),
                      np.dtype(np.int64)):
            # flat output index: SeqArray fetches expand to two outputs,
            # so fetch_names does not map 1:1 — name what we can
            raise ValueError(
                f"export_stablehlo: output #{i} (of fetches "
                f"{list(fetch_names)}) has dtype {dt}, unsupported by the "
                f"native runner ABI (cast the fetch target before saving)")
        out_metas.append({"shape": [int(d) for d in o.shape],
                          "dtype": str(dt)})
    meta = {"inputs": metas, "params": param_metas, "outputs": out_metas}
    _atomic_write(os.path.join(dirname, "model.stablehlo"),
                  module_text.encode())
    _atomic_write(os.path.join(dirname, "model.stablehlo.json"),
                  json.dumps(meta).encode())

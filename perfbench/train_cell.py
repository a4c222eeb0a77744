"""A training cell: the configuration's program through ``fluid.Executor``,
one compiled step with its state, driven from the seed through its first
steps (which the plain reference follows) and then handed, the same
object, to the measured window.

Recipe (``chip_smoke.build_train``'s, the one the repo's earlier records
used): fused flash attention, no materialised attention bias, fused vocab
loss, bfloat16 activations, Adam.

The plain reference follows the same first steps in this process AFTER the
window has closed and the program's state is freed: the program's readings
(losses, gradient and change norms) are host numbers by then, the device's
memory peak was noted as the window closed, and no second process has to
reach the chip first.  The position tables are as long as the mix's
``seq_len`` + 1, so a longer mix is a new traffic file and nothing else.
"""

from __future__ import annotations

import contextlib
import gc
import statistics
import time
from typing import Dict, List

import numpy as np

from . import flops, traffic, weights
from .reference import transformer as ref

MODEL_KEYS = ("n_layer", "n_head", "d_key", "d_value", "d_model",
              "d_inner_hid")
ADAM_BETA1 = ref.ADAM["beta1"]


def sized(cfg: Dict, mix: Dict) -> Dict:
    """The configuration at this mix's length: position tables of
    ``seq_len`` + 1 rows, and the mix's own limits where it states any
    (each cell's limits are set from that cell's readings)."""
    return dict(cfg, max_length=int(mix["seq_len"]) + 1,
                check={**cfg["check"], **mix.get("check", {})})


def build_program(cfg: Dict, seq_len: int, learning_rate: float):
    from paddle_tpu import fluid
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        avg_cost, _, _ = T.transformer(
            src_vocab_size=cfg["src_vocab_size"],
            trg_vocab_size=cfg["trg_vocab_size"],
            max_length=cfg["max_length"], dropout_rate=cfg["dropout"],
            src_seq_len=seq_len, trg_seq_len=seq_len, fused=True,
            materialize_attn_bias=False, fused_vocab_loss=True,
            amp_dtype=cfg["amp_dtype"], param_prefix=cfg["param_prefix"],
            **{k: cfg[k] for k in MODEL_KEYS})
        fluid.optimizer.Adam(learning_rate=learning_rate).minimize(avg_cost)
    return main, startup, avg_cost


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              floor: Dict[str, float] = None) -> Dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or a floor, whichever is larger.  The floor is the median
    leaf's norm (some gradients are all but zero) unless one is given per
    leaf."""
    med = statistics.median(want.values())
    return {k: abs(got[k] - want[k])
            / max(want[k], floor[k] if floor else med, 1e-30) for k in want}


def adam_floor(shapes: Dict, steps: int, learning_rate: float) -> Dict[str, float]:
    """Half the largest change Adam could have made to each leaf in
    ``steps`` steps (steps x lr x sqrt(elements)).  Adam's normalised step
    turns the rounding noise of an all-but-zero gradient into a full-size
    change, so the change of such a leaf (the last decoder layer's
    self-attention q and k at seeded weights: 0.21-0.43 of the reference's
    own norm over 12 seeds on the chip, PR 23) is measured against what
    the optimizer could have done, not against the little it did."""
    return {k: 0.5 * steps * learning_rate * float(np.prod(v)) ** 0.5
            for k, v in shapes.items()}


def token_loss_rms_gap(got, want) -> float:
    """Root mean square, over the first step's tokens, of the gap between
    the program's loss of a token and the reference's, as a share of the
    mean loss.  Unlike a norm or a mean it does not let rounding errors of
    opposite sign cancel, so it is the number a lower precision moves
    (float8 control against bfloat16 program, section 2 of PERF.md)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got.reshape(want.shape) - want) ** 2))
                 / np.mean(want))


def compare(run_losses, run_grad, run_delta, run_tokens, ref_out, limits,
            floor) -> List[Dict]:
    ref_losses, ref_grad, ref_delta, ref_tokens = ref_out
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(run_losses, ref_losses))
    return [
        {"name": "token_loss_rms_gap",
         "value": token_loss_rms_gap(run_tokens, ref_tokens),
         "limit": limits["token_loss_rms_gap"]},
        {"name": "loss_gap_max", "value": loss_gap,
         "limit": limits["loss_gap_max"]},
        {"name": "grad_norm_gap_worst_leaf",
         "value": max(leaf_gaps(run_grad, ref_grad).values()),
         "limit": limits["grad_norm_gap_worst_leaf"]},
        {"name": "delta_norm_gap_worst_leaf",
         "value": max(leaf_gaps(run_delta, ref_delta, floor).values()),
         "limit": limits["delta_norm_gap_worst_leaf"]},
    ]


class TrainStep:
    """THE compiled step with its state: built once in set-up, driven
    through its first steps, then timed."""

    def __init__(self, cfg: Dict, mix: Dict, batches, seed: int):
        from paddle_tpu import fluid, parallel

        self.batches = batches
        self.main, startup, self.avg_cost = build_program(
            cfg, int(mix["seq_len"]), float(cfg["learning_rate"]))
        self.exe = fluid.Executor(fluid.TPUPlace(0))
        self.scope = fluid.Scope()
        self.fluid = fluid
        axes = {k: int(v) for k, v in (mix.get("mesh_axes") or {}).items()}
        self.mesh = parallel.make_mesh(axes) if axes else None
        self._mesh_guard = parallel.mesh_guard
        # the per-token loss, fetched beside the mean in EVERY step (64 KB),
        # so that the checked steps and the timed ones are one executable
        self.token_cost = next(
            op.output("Loss")[0] for op in self.main.global_block().ops
            if op.type == "fused_vocab_cross_entropy")
        self.fetch = [self.avg_cost, self.token_cost]
        self.params = {p.name: tuple(p.shape)
                       for p in self.main.global_block().all_parameters()}
        want = ref.param_shapes(cfg, cfg["param_prefix"])
        if self.params != want:
            odd = sorted(set(self.params) ^ set(want))[:6]
            raise RuntimeError(f"the program's parameters are not the "
                               f"configuration's: {odd}")
        with fluid.scope_guard(self.scope):
            self.exe.run(startup)           # creates the optimizer's state
        self.seed = seed
        for name, value in weights.make(self.params, seed).items():
            self.scope.set_var(name, value)
        self.n_steps = 0

    def dispatch(self, index: int):
        """One optimizer step on batch ``index`` of the pool; returns the
        mean loss and the per-token losses, still on the device."""
        feed = self.batches[index % len(self.batches)]
        guard = contextlib.nullcontext() if self.mesh is None \
            else self._mesh_guard(self.mesh)
        with self.fluid.scope_guard(self.scope), guard:
            out = self.exe.run(self.main, feed=feed, fetch_list=self.fetch,
                               return_numpy=False)
        self.n_steps += 1
        return out

    def step(self, index: int) -> float:
        return float(np.asarray(self.dispatch(index)[0]))

    def misses(self) -> int:
        return int(self.exe.cache_stats()["executable"]["misses"])

    def _state(self, suffix: str) -> Dict[str, object]:
        out = {}
        for name in self.params:
            found = [v for v in self.scope.vars
                     if v.startswith(f"{name}_{suffix}")]
            if len(found) != 1:
                raise RuntimeError(f"optimizer state {suffix} of {name}: "
                                   f"found {found}")
            out[name] = self.scope.find_var(found[0])
        return out

    def first_gradient_norms(self) -> Dict[str, float]:
        """After exactly one step Adam's first moment is (1 - beta1) g."""
        norms = ref.leaf_norms(self._state("moment1"))
        return {k: float(v) / (1.0 - ADAM_BETA1) for k, v in norms.items()}

    def change_norms(self) -> Dict[str, float]:
        now = {n: self.scope.find_var(n) for n in self.params}
        start = weights.make(
            self.params, self.seed,
            sharding=None if self.mesh is None else self._replicated())
        return {k: float(v)
                for k, v in ref.leaf_diff_norms(now, start).items()}

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def state_devices(self) -> int:
        v = self.scope.find_var(next(iter(self.params)))
        return len(v.sharding.device_set)

    def free(self) -> None:
        """Give the device back before the reference runs: the state, and
        with the executor the compiled step and what it reserves."""
        import jax

        for value in list(self.scope.vars.values()):
            if isinstance(value, jax.Array) and not value.is_deleted():
                value.delete()
        self.exe = self.scope = self.main = self.batches = None
        gc.collect()


def reference_readings(cfg: Dict, mix: Dict, batches, seed: int,
                       precision: str = "float32"):
    """(losses, first-gradient norms, parameter-change norms, the first
    step's token losses) over the first ``check_steps`` steps, in blocks
    of ``reference_block_rows`` rows."""
    shapes = ref.param_shapes(cfg, cfg["param_prefix"])
    return ref.train_steps(
        lambda: weights.make(shapes, seed), cfg["param_prefix"], cfg,
        batches[:int(mix["check_steps"])], float(cfg["learning_rate"]),
        int(mix["reference_block_rows"]), precision,
        bool(mix.get("reference_remat", False)))


def run(ctx) -> Dict:
    """Drive one training cell; ``ctx`` is ``perfbench.run.Context``."""
    mix, spans = ctx.mix, ctx.spans
    cfg = sized(ctx.cfg, mix)
    batches = traffic.train_batches(mix, cfg["src_vocab_size"], ctx.seed)
    n_check = int(mix["check_steps"])

    with spans.span("build"):
        step = ctx.make_step(cfg, mix, batches, ctx.seed)
    with spans.span("warm"):
        first, run_tokens = step.dispatch(0)
        losses = [float(np.asarray(first))]
        run_tokens = np.asarray(run_tokens)
        run_grad = step.first_gradient_norms()
        losses += [step.step(i) for i in range(1, n_check)]
        run_delta = step.change_norms()
        for i in range(n_check, n_check + int(mix.get("warm_steps", 2))):
            step.step(i)
        ctx.settle()
    misses0 = step.misses()

    # the window: whole steps, each ending in a fetched loss
    seconds = ctx.window_seconds()
    ctx.start_trace()
    window_losses: List[float] = []
    i = step.n_steps
    with spans.span("window"):
        t0 = time.monotonic()
        ctx.setup_done(t0)
        while True:
            with spans.span("dispatch"):
                out = step.dispatch(i)
            with spans.span("fetch_loss"):
                window_losses.append(float(np.asarray(out[0])))
            i += 1
            t1 = time.monotonic()
            if t1 - t0 >= seconds:
                break
    ctx.window_closed()
    elapsed = t1 - t0
    n = len(window_losses)
    tokens = n * int(mix["batch"]) * 2 * int(mix["seq_len"])
    bad = sum(1 for x in window_losses if not np.isfinite(x))
    compiles = step.misses() - misses0
    state_devices, params = step.state_devices(), step.params
    with spans.span("free"):
        step.free()

    # the reference, once the device is free again, on the same batches
    with spans.span("reference"):
        ref_out = reference_readings(cfg, mix, batches, ctx.seed)
    floor = adam_floor(params, n_check, float(cfg["learning_rate"]))
    control = None
    if ctx.control_precision:
        with spans.span("control"):
            c = reference_readings(cfg, mix, batches, ctx.seed,
                                   ctx.control_precision)
        control = compare(c[0], c[1], c[2], c[3], ref_out, cfg["check"],
                          floor)
    checks = compare(losses, run_grad, run_delta, run_tokens, ref_out,
                     cfg["check"], floor)
    checks += [
        {"name": "nonfinite_losses", "value": float(bad), "limit": 0.0},
        {"name": "compiles_in_window", "value": float(compiles),
         "limit": 0.0},
    ]
    chips = int(mix.get("chips", 1))
    if state_devices != chips:
        checks.append({"name": "state_spans_devices",
                       "value": float(abs(state_devices - chips)),
                       "limit": 0.0})
    step_flops = flops.train_step_flops(cfg, int(mix["batch"]),
                                        int(mix["seq_len"]),
                                        int(mix["seq_len"]))
    worst = {}
    for what, got, want, fl in (("grad", run_grad, ref_out[1], None),
                                ("delta", run_delta, ref_out[2], floor)):
        gaps = leaf_gaps(got, want, fl)
        leaf = max(gaps, key=gaps.get)
        worst[what] = [leaf, gaps[leaf], got[leaf], want[leaf]]
    starts = [t for name, t, _ in spans.records if name == "dispatch"]
    each = sorted(b - a for a, b in zip(starts, starts[1:]))
    mid = each[len(each) // 2] if each else 0.0
    info = {"steps": n, "window_s": elapsed, "worst_leaf": worst,
            "step_ms": 1e3 * elapsed / n, "step_ms_p50": 1e3 * mid,
            "step_ms_max": 1e3 * each[-1] if each else 0.0,
            "steps_over_1.25x": sum(1 for x in each if x > 1.25 * mid),
            "first_losses": losses, "reference_losses": ref_out[0],
            "last_loss": window_losses[-1],
            "reference_s": spans.total("reference"),
            "model_flops_per_step": step_flops,
            "parameters": weights.count(params)}
    if control:
        info["control"] = {"precision": ctx.control_precision,
                           **{c["name"]: c["value"] for c in control}}
    if ctx.peaks:
        info["model_flops_utilization"] = (
            step_flops * n / elapsed
            / (chips * ctx.peaks["bf16_flops_per_s"]))
    return {
        "e2e": {"train_tokens_per_s": tokens / elapsed},
        "attempted": n, "failed": bad, "checks": checks, "info": info,
        "layer": {"kind": "train", "steps": n, "window_s": elapsed,
                  "batch": int(mix["batch"]),
                  "seq_len": int(mix["seq_len"]), "chips": chips},
    }

"""Training cells: one compiled step with its state, driven from the seed
through its first three steps (which are the warm-up and what ``correct``
compares), then handed to the measured window.

The feed is ``staged_ring``: a ring of distinct batches staged on the device
in set-up; the window calls ``net._fit_batch`` (what ``fit()`` calls per
batch) back to back. Nothing here knows a model: the configuration's
``reference`` file makes the weights and the batches, follows the steps and
counts the operations; its ``builder`` file builds the program's net, puts
the weights in and reads its state out.

A listener keeps each step's loss on the device and reads it ``steps_ahead``
steps late (the mix's number: four to eight seconds of steps), so the chip
stays fed while the host stands still and every loss is still read. The
window closes so: when its time is up nothing more is sent, every step that
was sent is waited for, and the clock is read after that wait: all of that
work counts, over all of that time.
"""

from __future__ import annotations

import collections
import functools
import gc
import time

from chipbench import checks
from chipbench.manifest import module_from

FIRST_STEPS = 3


class StepClock:
    """TrainingListener: keeps each step's loss where the step left it, on
    the device, reads it (a host sync) once ``ahead`` later steps have been
    dispatched, and stamps that step's end then. ``ahead`` 0 reads every
    loss at once, as set-up's first steps do."""

    def __init__(self):
        self.ahead = 0
        self.pending = collections.deque()
        self.losses, self.ends = [], []

    def iteration_done(self, model, iteration, epoch):
        self.pending.append(model.score_value)
        self.read(leave=self.ahead)

    def read(self, leave: int = 0) -> None:
        """Wait for the oldest steps until ``leave`` are left in flight."""
        while len(self.pending) > leave:
            self.losses.append(float(self.pending.popleft()))
            self.ends.append(time.perf_counter())


@functools.lru_cache(maxsize=None)
def _norms_program():
    """One jitted program for all the leaves (built once: JAX is imported
    only when a cell runs)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        if b is not None:
            a = jax.tree_util.tree_map(jnp.subtract, a, b)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in a.items()}

    return norms


def _norms(tree: dict, minus: dict = None) -> dict:
    """Per-leaf norms of ``tree`` (or of ``tree - minus``)."""
    return {k: float(v) for k, v in _norms_program()(tree, minus).items()}


class Program:
    """The compiled step with its state and its batches: built once from the
    seed, driven through the first steps, then handed as it is to the
    window. ``keep`` names the leaves whose first gradient is kept for its
    direction: those the configuration's limits name, or (``"all"``, for
    the readings) every leaf."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int,
                 keep=None):
        self.cfg, self.chips = cfg, chips
        self.ref = module_from("reference", cfg["reference"])
        self.builder = module_from("builders", cfg["builder"])
        self.batch = int(cfg["per_chip_batch"]) * chips
        self.keep = tuple(cfg["limits"]["grad_angle"]) if keep is None \
            else keep
        self.weights = self.ref.make_weights(seed, cfg)
        self.net = self.builder.build(cfg)
        self.builder.load(self.net, self.weights)
        self.clock = StepClock()
        self.net.set_listeners(self.clock)
        self.ring = self.ref.make_batches(seed, cfg, int(mix["ring"]),
                                          self.batch)
        self.spans: dict = {}

    def step(self, item) -> None:
        """One step through the window's own call."""
        import jax

        with jax.profiler.TraceAnnotation("cb:step"):
            self.net._fit_batch(*item)

    def first_steps(self) -> dict:
        """Warm-up, and the program's side of ``correct``: the losses of the
        first steps, the first gradient as Adam got it (from its first
        moment after one step): every leaf's norm and the kept leaves
        themselves, and the norm of the parameters' change."""
        import jax.numpy as jnp

        self.step(self.ring[0])
        scale = 1.0 / (1.0 - self.ref.ADAM["beta1"])
        m1 = self.builder.adam_m(self.net)
        if self.keep == "all":
            self.keep = tuple(m1)
        got = {"grad_norms": {k: v * scale for k, v in _norms(m1).items()},
               # copies: the next step donates the optimizer's state
               "first_grads": {k: jnp.array(m1[k], copy=True)
                               for k in self.keep}}
        del m1
        for item in self.ring[1:FIRST_STEPS]:
            self.step(item)
        got["losses"] = list(self.clock.losses[:FIRST_STEPS])
        params = self.builder.export(self.net.params)
        got["change_norms"] = _norms(params, {k: self.weights[k]
                                              for k in params})
        return got

    def step_memory(self):
        """The compiled step's ``memory_analysis()`` where the builder can
        name the step (the jit's own cache answers: 0.0 s on the chip)."""
        how = getattr(self.builder, "step_memory", None)
        return how(self.net, *self.ring[0]) if how else None

    def reference_inputs(self):
        """(weights, the first steps' batches as float32); call after
        :meth:`free`."""
        import jax.numpy as jnp

        return self.weights, [(jnp.asarray(x, jnp.float32), jnp.asarray(y))
                              for x, y in self.ring[:FIRST_STEPS]]

    def free(self) -> None:
        """Drop the program's state from the device."""
        self.net.set_listeners()
        self.net = None
        self.ring = self.ring[:FIRST_STEPS]
        gc.collect()


def follow_reference(prog: Program, **how) -> dict:
    """The plain reference (or, with ``lower`` or ``fault``, the control or
    a planted fault in its place) through the program's first steps."""
    import jax

    weights, first = prog.reference_inputs()
    with jax.default_matmul_precision("highest"):
        return prog.ref.follow(weights, first, keep=prog.keep, **how)


def run(ctx, planted=None) -> dict:
    """One run of a training cell. ``planted`` is for the tests under
    tests/chipbench_tests: a function that breaks the timed path after it is
    built (test_chipbench_run_train.py)."""
    import jax

    cfg = ctx.cell.cfg
    prog = Program(cfg, ctx.cell.mix, ctx.seed, ctx.cell.chips)
    if planted is not None:
        planted(prog)
    got = prog.first_steps()
    ctx.mark_setup_done()

    # ---- the measured window
    clock, ring = prog.clock, prog.ring
    n0 = len(clock.ends)
    tracer = ctx.tracer()
    rolled = ring[FIRST_STEPS % len(ring):] + ring[:FIRST_STEPS % len(ring)]
    clock.ahead = int(ctx.cell.mix.get("steps_ahead", 0))
    t0 = time.perf_counter()
    with tracer:
        i = 0
        while time.perf_counter() - t0 < ctx.seconds:
            tracer.poll()
            prog.step(rolled[i % len(rolled)])
            i += 1
        clock.read()                 # nothing more is sent: wait for all
    jax.block_until_ready(prog.net.params)
    elapsed = time.perf_counter() - t0
    ctx.mark_window_done()
    steps = len(clock.ends) - n0
    ends = clock.ends[n0:]
    spans = prog.spans
    spans["step_wall"] = [b - a for a, b in zip(ends, ends[1:])]
    peak = ctx.memory_peak_bytes(prog.step_memory())

    # ---- free the program's state, then follow the reference
    prog.free()
    want = follow_reference(prog)
    numbers = checks.training_numbers(got, want, cfg["limits"])
    rate = steps * prog.batch / elapsed / prog.chips
    return {
        "attempted": steps, "failed": 0,
        "numbers": numbers,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_images_per_s_per_chip": rate},
        "run": {"spans": spans, "steps": steps, "batch": prog.batch,
                "elapsed_s": elapsed, "images_per_s_per_chip": rate},
    }

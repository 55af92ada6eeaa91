"""Traffic kind ``train_steps``: a training script's loop.

Cycles the cell's seeded host batches through the family's trainer, one
un-waited step after another, reading the loss every ``loss_read_every``
steps and at the window's end, as a training script logs it.  The cell's
file gives the sizes; ``benchmarks/models/<family>.py`` makes the batches
and builds the trainer; nothing here knows the model.

Set-up builds ONE trainer, drives it through its first ``verify_steps``
steps on the first host batches (rows that all differ) through this same
call and feed, reads what ``correct`` compares, and hands that same object
to the window.  ``ctx.on_built`` (the tests' planted faults) is called with
this object once the trainer exists and before its first step.
"""
import time

VERIFY_STEPS = 3


class Kind:
    def __init__(self, ctx):
        self.ctx = ctx
        self.traffic = ctx.cell["traffic_params"]
        self.trainer = None
        self.readings = None

    # -- set-up: build, compile, first steps -------------------------------
    def setup(self):
        ctx, spans = self.ctx, self.ctx.spans
        self.batches = ctx.model.make_batches(ctx.cfg, self.traffic, ctx.seed)
        ctx.log("host batches made")
        self.trainer = ctx.model.build_trainer(ctx.cfg, self.traffic,
                                               ctx.seed, ctx.log)
        ctx.log("trainer built")
        if ctx.on_built is not None:
            ctx.on_built(self)
        tr = self.trainer
        self.grad_leaves = tuple(ctx.cell["limits"].get("grad_vector", ()))
        losses = []
        for i in range(VERIFY_STEPS):
            losses.append(tr.loss_value(tr.step(self.batches[i])))
            ctx.log(f"step {i + 1} done, loss {losses[-1]:.5f}")
            if i == 0:
                gnorm = tr.first_gradient_norms()
                gvec = tr.first_gradient_vectors(self.grad_leaves)
        self.readings = {"loss": losses, "grad_norm": gnorm,
                         "grad_vector": gvec,
                         "change_norm": tr.change_norms()}
        # one more read in the window's own rhythm, so nothing is new there
        tr.loss_value(tr.step(self.batches[VERIFY_STEPS % len(self.batches)]))
        self.step_no = VERIFY_STEPS + 1
        ctx.log("readings taken")
        spans.reset()

    # -- the measured window ------------------------------------------------
    def _drive(self, seconds):
        """Steps for ``seconds``; the last one is waited for.  Returns
        (steps finished, seconds the whole took, last loss)."""
        tr, spans, batches = self.trainer, self.ctx.spans, self.batches
        every = self.traffic["loss_read_every"]
        n, loss = 0, None
        self.first_step_no = self.step_no
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with spans.span("step"):
                loss = tr.step(batches[self.step_no % len(batches)])
            self.step_no += 1
            n += 1
            if n % every == 0:
                with spans.span("loss_read"):
                    self.last_loss = tr.loss_value(loss)
        with spans.span("loss_read"):
            self.last_loss = tr.loss_value(loss)
        return n, time.perf_counter() - t0

    def _batches_driven(self, steps):
        return [self.batches[i % len(self.batches)] for i in
                range(self.first_step_no, self.first_step_no + steps)]

    def window(self, seconds):
        steps, took = self._drive(seconds)
        batch = self.traffic["batch"]
        self.steps, self.window_s = steps, took
        self.window_batches = self._batches_driven(steps)
        self.window_spans = dict(self.ctx.spans.durations)
        return {"attempted": steps, "failed": 0 if _finite(self.last_loss)
                else steps,
                "end_to_end": {"train_samples_per_s": steps * batch / took}}

    def traced(self, seconds):
        """The same loop for a few seconds more, under the profiler."""
        steps, took = self._drive(seconds)
        self.traced_batches = self._batches_driven(steps)
        return took

    def finish(self):
        pass

    def free(self):
        if self.trainer is not None:
            self.trainer.free()
        self.trainer = None

    # -- correct --------------------------------------------------------------
    def verify(self):
        """The reference follows the same first steps; see
        ``benchmarks/harness/compare.py`` for what is compared."""
        from ..harness import compare
        ctx = self.ctx
        want = ctx.reference.train_readings(
            ctx.cfg, ctx.seed, self.batches, ctx.cfg["learning_rate"],
            steps=VERIFY_STEPS, grad_leaves=self.grad_leaves)
        return compare.training(self.readings, want, ctx.cell["limits"],
                                last_loss=self.last_loss)


def _finite(v):
    return v is not None and v == v and abs(v) != float("inf")

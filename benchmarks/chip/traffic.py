"""The one traffic generator: drives the program as a mix file says.

A mix (``traffic/<name>.json``) is data. Its ``loop`` picks how the
program is driven, and its other keys set the sizes:

``train``: a closed loop over the train split's batches in stream order,
each with the program's train negatives, through the pipeline's public
``train_batches()`` and ``train_step``. Set-up runs the hooks alone over
the first ``start_batch`` batches (so neighborhoods are warm, as in a run
resumed mid-epoch), then ``check_steps`` steps that go through the
window's own call and feed and are the steps the reference checks; the
window continues from there. An exhausted split starts a new pass after
``reset_epoch_state()``, as ``train_epoch`` does; set-up compiles the
step as a new pass first calls it, so nothing compiles in the window. The
window closes on
``block_until_ready`` of the last step's outputs.

``eval``: one-vs-many ranking of each positive edge of ``splits`` against
the program's eval negatives, batch by batch, each batch ending in its
scores read on the host, through the loader, hook key and eval step that
``evaluate`` uses. Set-up makes ``evaluate``'s warm pass through
``warm_split`` and saves the hook state; ``warm_batches`` eval batches
then compile and warm the step, and the saved state is restored. When the
window has used every split it restores the saved state and starts over
(inside the window). After the window, ``check_batches`` batches drawn
from the seed are checked against the reference.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chip.reference import check as ref_check


def _host(x):
    return np.asarray(x)


class Mix:
    """Shared state of one run's traffic."""

    def __init__(self, pipe, data, mix: dict, config: dict, keep_keys=()):
        self.pipe = pipe
        self.data = data
        self.mix = mix
        self.config = config
        self.stateful = getattr(pipe, "model_state", None) is not None
        self.batch = pipe.batch_size
        # Traced runs keep host copies of these batch arrays for the work
        # counts (copied asynchronously, converted one batch later).
        self.keep_keys = tuple(keep_keys)
        self.kept, self._pending = [], None
        self.records = {}

    def _keep(self, bt):
        if not self.keep_keys:
            return
        if self._pending is not None:
            self.kept.append({k: _host(v) for k, v in self._pending.items()})
        self._pending = {k: bt[k] for k in self.keep_keys}
        for v in self._pending.values():
            if isinstance(v, jax.Array):
                v.copy_to_host_async()

    def flush_kept(self):
        if self._pending is not None:
            self.kept.append({k: _host(v) for k, v in self._pending.items()})
            self._pending = None
        return self.kept

    def release(self):
        """Drop every reference to the program's state."""
        self.pipe = None


class TrainMix(Mix):
    loop = "train"

    def _batches(self):
        pipe = self.pipe
        pipe.reset_epoch_state()
        self._index = 0
        self._iter = pipe.train_batches()

    def _next(self):
        """The next staged batch and its count of real events."""
        try:
            bt = next(self._iter)
        except StopIteration:
            self._batches()
            bt = next(self._iter)
        n_train = len(self.pipe.train_data.src)
        events = min(self.batch, n_train - self.batch * self._index)
        self._index += 1
        return bt, events

    def _step(self, bt):
        p = self.pipe
        if self.stateful:
            p.params, p.opt_state, p.model_state, loss = p.train_step(
                p.params, p.opt_state, p.model_state, bt)
        else:
            p.params, p.opt_state, loss = p.train_step(
                p.params, p.opt_state, bt)
        return loss

    def _warm_new_pass(self, bt, fresh):
        """Compile the step as the window calls it after a pass boundary:
        trained parameters with the model state that
        ``reset_epoch_state()`` makes, whose arrays are uncommitted and so
        a separate entry of the jit cache. The outputs are dropped: the
        program's state is unchanged."""
        p = self.pipe
        jax.block_until_ready(p.train_step(p.params, p.opt_state, fresh, bt))

    def _warm_pass_end(self):
        """Run the train hooks once over the split's last, short batch, so
        that the eager operations of its shape compile in set-up. The pass
        that follows resets the hook state this leaves."""
        from repro.core.recipes import TRAIN_KEY

        p = self.pipe
        n = len(p.train_data.src)
        if n % self.batch == 0:
            return
        tail = p.train_data.slice_events(n - n % self.batch, n)
        with p.manager.activate(TRAIN_KEY):
            for batch in p._loader(tail):
                jax.block_until_ready(p._batch_tensors(batch))

    def setup(self):
        self._warm_pass_end()
        self._batches()
        fresh = getattr(self.pipe, "model_state", None)
        for _ in range(self.mix["start_batch"]):
            self._next()
        b1 = self.config["optimizer"]["b1"]
        start = self.pipe.params
        steps, losses = [], []
        for i in range(self.mix["check_steps"]):
            bt, _ = self._next()
            lo = self.batch * (self._index - 1)
            steps.append({"lo": lo, "src": _host(bt["src"]),
                          "dst": _host(bt["dst"]), "time": _host(bt["time"]),
                          "mask": _host(bt["batch_mask"]).astype(bool),
                          "neg": _host(bt["neg"])})
            losses.append(self._step(bt))
            if i == 0:
                grad = ref_check.leaf_norms(jax.tree.map(
                    lambda m: m / (1.0 - b1), self.pipe.opt_state["mu"]))
        change = ref_check.leaf_norms(jax.tree.map(
            lambda a, b: a - b, self.pipe.params, start))
        self.check_steps = steps
        self.program = {"losses": [float(x) for x in losses], "grad": grad,
                        "change": change}
        if self.stateful:
            self._warm_new_pass(bt, fresh)

    def window(self, seconds: float) -> dict:
        waits, losses, events = [], [], 0
        t0 = time.perf_counter()
        while True:
            w0 = time.perf_counter()
            with TraceAnnotation("bench/wait"):
                bt, n = self._next()
            waits.append(time.perf_counter() - w0)
            with TraceAnnotation("bench/step"):
                losses.append(self._step(bt))
            self._keep(bt)
            events += n
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench/drain"):
            jax.block_until_ready((self.pipe.params, self.pipe.opt_state,
                                   losses))
        window_s = time.perf_counter() - t0
        self.records = {"window_s": window_s, "steps": len(waits),
                        "waits": waits, "events": events,
                        "loss": float(losses[-1])}
        return self.records

    def end_to_end(self) -> dict:
        r = self.records
        return {"train_events_per_s": r["events"] / r["window_s"]}

    def attempted(self) -> int:
        return self.records["steps"]

    def release(self):
        if getattr(self, "_iter", None) is not None:
            self._iter.close()
            self._iter = None
        super().release()

    def check(self, stream, params, dtype, seed: int) -> dict:
        """The program's checked steps against the reference's."""
        ref = ref_check.train(self.config["model"], self.config["optimizer"],
                              stream, self.check_steps, params, dtype,
                              num_nodes=self.data.num_nodes)
        return ref_check.train_gaps(self.program, ref)


class EvalMix(Mix):
    loop = "eval"

    def _split(self, name):
        return {"train": self.pipe.train_data, "val": self.pipe.val_data,
                "test": self.pipe.test_data}[name]

    def _eval(self, bt):
        p = self.pipe
        if self.stateful:
            out, p.model_state = p._eval_step(p.params, p.model_state, bt)
            return out
        return p._eval_step(p.params, bt)

    def _save(self):
        p = self.pipe
        self._saved = (p.manager.state_dict(),
                       p.model_state if self.stateful else None)

    def _restore(self):
        from repro.core.tg_hooks import TGBEvalNegativesHook

        p = self.pipe
        hooks, state = self._saved
        p.manager.load_state_dict(hooks)
        if self.stateful:
            p.model_state = state
        for h in p.manager.hooks():
            if isinstance(h, TGBEvalNegativesHook):
                h.reset_state()

    def _passes(self):
        """Yield (split, batch index, first event index, staged batch)
        over the mix's splits, forever; between full passes the saved
        state is restored."""
        from repro.core.recipes import EVAL_KEY

        p = self.pipe
        while True:
            for name in self.mix["splits"]:
                data = self._split(name)
                lo0 = int(getattr(data, "eid_offset", 0))
                with p.manager.activate(EVAL_KEY), contextlib.closing(
                        iter(p._loader(data))) as it:
                    for i, bt in enumerate(it):
                        yield name, i, lo0 + self.batch * i, p._batch_tensors(bt)
            with TraceAnnotation("bench/restore"):
                self._restore()

    def setup(self):
        from repro.core.recipes import TRAIN_KEY

        p = self.pipe
        p.reset_epoch_state()
        with p.manager.activate(TRAIN_KEY):
            for batch in p._loader(self._split(self.mix["warm_split"])):
                if self.stateful:
                    self._eval(p._batch_tensors(batch))
        self._save()
        feed = self._passes()
        for _ in range(self.mix["warm_batches"]):
            _, _, _, bt = next(feed)
            jax.block_until_ready(self._eval(bt))
        feed.close()
        self._restore()

    def window(self, seconds: float) -> dict:
        lat, waits, queries, seen = [], [], 0, []
        feed = self._passes()
        t0 = time.perf_counter()
        while True:
            r0 = time.perf_counter()
            with TraceAnnotation("bench/wait"):
                split, i, lo, bt = next(feed)
            waits.append(time.perf_counter() - r0)
            with TraceAnnotation("bench/step"):
                pos, neg = self._eval(bt)
            with TraceAnnotation("bench/read"):
                pos, neg = _host(pos), _host(neg)
            now = time.perf_counter()
            lat.append(now - r0)
            n = min(self.batch, len(self._split(split).src) - self.batch * i)
            queries += n
            seen.append({"split": split, "lo": lo, "events": n,
                         "neg": bt["neg"], "pos_score": pos,
                         "neg_score": neg})
            self._keep(bt)
            if now - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        feed.close()
        self.seen = seen
        self.records = {"window_s": window_s, "steps": len(lat),
                        "latencies": lat, "waits": waits,
                        "queries": queries}
        return self.records

    def end_to_end(self) -> dict:
        r = self.records
        lat_ms = np.asarray(r["latencies"]) * 1e3
        return {"eval_queries_per_s": r["queries"] / r["window_s"],
                "eval_batch_p90_ms": float(np.percentile(lat_ms, 90))}

    def attempted(self) -> int:
        return self.records["queries"]

    def check(self, stream, params, dtype, seed: int,
              stand_in=None) -> dict:
        """Widest gap between the program's and the reference's score of
        any valid query in ``check_batches`` window batches drawn from
        ``seed``. ``stand_in``: a dtype at which the reference takes the
        program's place (the control)."""
        rng = np.random.default_rng(seed)
        n = min(self.mix["check_batches"], len(self.seen))
        picks = sorted(rng.choice(len(self.seen), size=n, replace=False))
        gap = 0.0
        for j in picks:
            rec = self.seen[j]
            lo, m = rec["lo"], rec["events"]
            batch = self._batch_inputs(lo, m, _host(rec["neg"]))
            want = ref_check.eval_scores(self.config["model"], stream, batch,
                                         params, dtype)
            got = (rec["pos_score"], rec["neg_score"])
            if stand_in is not None:
                got = ref_check.eval_scores(self.config["model"], stream,
                                            batch, params, stand_in)
            gap = max(gap, ref_check.score_gap(got, want, m))
        return {"score_gap": gap}

    def _batch_inputs(self, lo: int, m: int, neg) -> dict:
        b = self.batch
        src = np.zeros(b, np.int64)
        dst = np.zeros(b, np.int64)
        t = np.zeros(b, np.int64)
        src[:m] = self.data.src[lo:lo + m]
        dst[:m] = self.data.dst[lo:lo + m]
        t[:m] = self.data.edge_t[lo:lo + m]
        return {"lo": lo, "src": src, "dst": dst, "time": t,
                "mask": np.arange(b) < m, "neg": np.asarray(neg, np.int64)}


MIXES = {"train": TrainMix, "eval": EvalMix}

"""The ``fitloop`` path: ``fit.FitLoop``, the resilient training driver,
composed (no checkpoint directory, no heartbeat, the loss scale held at 1)
round the ``gluon`` path's net and ``gluon.Trainer``. The loop that users of
the NaN sentinel and the preemption-safe exit run: per step the forward,
backward, all-reduce and sentinel update of the Gluon loop, then one fetch
of the finite flag and the loss, the step breakdown's clock reads and the
memory pressure check.

``fit()`` runs beside the harness, on a thread of its own, and trains on the
batches a ``DataIter`` hands it: ``dispatch(i)`` releases batch i to it and
``wait`` returns that step's loss from ``on_step_end``. ``FitLoop`` fetches
the loss every step, so nothing is in flight between its steps; with the
traffic's ``ahead`` of 2 the iterator holds one batch ready and ``fit()``
never waits for the harness. ``scale_growth_interval=0``: the traffic is
bf16 with float32 masters and needs no loss scaling, and with the default of
200 the scale would double inside the window and every program that bakes
it in would compile again there.
"""
import queue
import threading

import jax.numpy as jnp

import gluon as gluon_path


class Released:
    """A ``DataIter`` over the pool that blocks until the harness releases
    the next batch."""

    def __init__(self, batches):
        from mxnet_tpu.io import DataBatch
        self.batches = [DataBatch(data=[d], label=[l]) for d, l in batches]
        self.released = queue.Queue()

    def reset(self):
        pass

    def __iter__(self):
        return self

    def __next__(self):
        return self.batches[self.released.get()]


class Path(gluon_path.Path):
    def __init__(self, config, traffic, seed, devices):
        super().__init__(config, traffic, seed, devices)
        from mxnet_tpu.fit import FitLoop
        self.feed = Released(self.batches)
        self.done = queue.Queue()
        loop = FitLoop(
            self.net, self.trainer,
            lambda out, label: self.loss_fn(out.astype("float32"), label),
            self.feed, ckpt_dir=None, heartbeat=False,
            scale_growth_interval=0,
            on_step_end=lambda step, loss: self.done.put(loss))
        self.thread = threading.Thread(
            target=self._fit, args=(loop,), daemon=True)

    def _fit(self, loop):
        try:
            loop.fit(1, batch_size=self.batch)
        except BaseException as e:  # hand it to the thread that waits
            self.done.put(e)
            raise

    def dispatch(self, i):
        if not self.thread.is_alive():
            self.thread.start()
        self.feed.released.put(i % len(self.feed.batches))

    def wait(self, _):
        loss = self.done.get()
        if isinstance(loss, BaseException):
            raise RuntimeError("fit() ended") from loss
        return jnp.float32(loss)

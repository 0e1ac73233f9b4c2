"""Faults planted under the timed path, for tests: each gets the prepared run
(``harness.fit_cell.Prepared``) before the first step and breaks the program
underneath the harness. `correct` has to come out false for every one."""

from __future__ import annotations


def state_unchanged(prep) -> None:
    """Every step computes its loss and returns its state unchanged."""
    eng = prep.est.engine
    real = eng.train_batch

    def frozen(batch):
        snap = eng.snapshot()
        loss = real(batch)
        step = eng.step
        eng.restore_snapshot(snap)
        eng.step = step
        return loss

    eng.train_batch = frozen


def _rows_left_out(keep_one_in: int):
    def tamper(prep) -> None:
        import jax
        from analytics_zoo_tpu.orca.learn.utils import Batch
        eng = prep.est.engine
        real = eng.train_batch

        def partial(batch):
            rows = batch.x[0].shape[0] // keep_one_in
            cut = jax.jit(lambda t: jax.tree.map(lambda a: a[:rows], t))
            return real(Batch(x=cut(batch.x), y=cut(batch.y), w=None))

        eng.train_batch = partial
    return tamper


# half of the batch left out, the mean taken over the rest
half_batch = _rows_left_out(2)
# on four chips: one chip's shard alone decides the update, which is what the
# step computes with the exchange between chips left out
no_exchange = _rows_left_out(4)


def altered_row(prep) -> None:
    """The infeed delivers one pixel of one row altered."""
    pipe = prep.pipeline
    real = pipe._host_batches

    def altered(shuffle):
        for b in real(shuffle):
            b.x[0][0, 0, 0, 0] ^= 1
            yield b

    pipe._host_batches = altered

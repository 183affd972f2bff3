"""Host time per superstep that the training loop spent blocked on the
prefetch feed (``launch/train.py::PrefetchFeed`` over
``put_worker_sharded``), by the harness's host clock."""


def read(ctx):
    waits = ctx.feed_wait_s
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3

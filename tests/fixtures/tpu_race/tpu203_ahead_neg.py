"""TPU203 negative, the ahead order: step N+1 is launched before step
N is read; the host then waits for step N and releases the lanes that
retired in step N — the last step dispatched over them has completed,
though step N+1 (which does not hold them) is outstanding."""
import jax


class Engine:
    def __init__(self, cache):
        self.cache = cache
        self._inflight = None

    def step(self, work):
        prev = self._inflight
        self._inflight = self._plain_dispatch(work)
        if prev is None:
            return
        self._complete(prev)

    def _complete(self, inflight):
        jax.block_until_ready(inflight.out)
        for slot in inflight.retired:
            self._release(slot)

    def _release(self, slot):
        self.cache.free(slot.blocks)

    def _plain_dispatch(self, work):
        return work

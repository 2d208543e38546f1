"""TPU203 positive, the ahead order gone wrong: step N+1 is launched
before step N is read (sound), but lanes are released between a
dispatch and THAT dispatch's completion — first step N's lanes before
the wait on step N, then the lanes of step N+1, which is still
running."""
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
        for slot in prev.retired:
            self.cache.free(slot.blocks)
        jax.block_until_ready(prev.out)
        for slot in self._inflight.retired:
            self.cache.free(slot.blocks)

    def _plain_dispatch(self, work):
        return work

"""Delivery paths a cell's ``path`` names: each module has ``open_source``
returning a :class:`Source` (the loader, the processes it started, close)."""


class Source:
    def __init__(self, loader, pids=(), close=None):
        self.loader, self.pids = loader, tuple(pids)
        self._close = close

    def close(self):
        stop = getattr(self.loader, "stop", None)
        if stop is not None:
            stop()
            self.loader.join()
        if self._close is not None:
            self._close()

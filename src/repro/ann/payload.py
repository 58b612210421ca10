"""The rows an IVF index scores, held in one place.

An IVF index keeps its payload grouped by cluster: float32 vectors
``(n, d)`` for IVF-flat, uint8 PQ codes ``(n, m)`` for IVF-PQ.  Building,
appending and loading produce it as a numpy array; the first batched
search places it on the device with one ``jnp.asarray`` (span
``scan.payload_put``) and drops the numpy array, so the rows live in HBM
once and the scan gathers each block's arena there.  The few host
readers (the oracle, ``add``, save, the shard planner) pull a copy with
:meth:`PayloadStore.host`, once a call.  Nothing here imports jax until
the rows are placed.
"""

from __future__ import annotations

import numpy as np

from .stats import span

__all__ = ["PayloadStore"]


class PayloadStore:
    """The cluster-grouped rows of an IVF index, on the host or the device.

    Exactly one of the two copies exists at any time: ``place`` moves the
    rows to the device and forgets the numpy array.  A pickled store
    carries numpy rows and comes back on the host.
    """

    def __init__(self, rows: np.ndarray):
        self._host = np.ascontiguousarray(rows)
        self._dev = None

    def _rows(self):
        return self._host if self._host is not None else self._dev

    @property
    def shape(self) -> tuple:
        return tuple(self._rows().shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._rows().dtype)

    @property
    def nbytes(self) -> int:
        return int(self._rows().nbytes)

    @property
    def on_device(self) -> bool:
        return self._dev is not None

    def host(self) -> np.ndarray:
        """The rows as a numpy array: the held one, or a pull of the
        device copy (read-only, and not kept)."""
        if self._host is not None:
            return self._host
        return np.asarray(self._dev)

    def place(self) -> int:
        """Put the rows on the device unless they are there already.

        Returns the bytes moved (0 when they were on the device).
        """
        if self._host is None:
            return 0
        import jax.numpy as jnp

        with span("scan.payload_put"):
            dev = jnp.asarray(self._host)
            dev.block_until_ready()
        self._host, self._dev = None, dev
        return int(dev.nbytes)

    @property
    def device(self):
        """The device copy; :meth:`place` must have run."""
        if self._dev is None:
            raise RuntimeError("payload not placed on the device")
        return self._dev

    def __getstate__(self) -> dict:
        return {"rows": self.host()}

    def __setstate__(self, state: dict) -> None:
        self.__init__(np.array(state["rows"]))

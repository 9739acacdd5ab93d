"""The store's image export as a stream of row chunks: the host side of
``JaxBackend.iter_ion_images`` (ISSUE 49).

The device program (``ops/imager_jax.export_image_chunks``) hands the
export over in pieces of ``ops/buckets.EXPORT_CHUNK_BYTES``; what is here
plans the calls, starts the first, and yields the pieces as they land, so
the writer (``engine/storage.py::store_ion_images``) compresses and writes
one while the next is on the link.

It lives outside ``models/msm_jax.py`` for a reason the code does not show:
the Mosaic payloads of the moments and chaos kernels carry the file and
LINE of up to ten frames of the stack that traced them, ``_dispatch``,
``score_batches`` and ``_enqueue_traced`` of that module among them, so a
line added anywhere above those call sites re-keys every scoring executable
in the persistent compile cache (40-80 s of set-up a cell and machine;
ROADMAP A6 f).  ``msm_jax.py`` keeps its jit call sites (``make_extract_jit``,
``_export_images``) at the line count they had."""

from __future__ import annotations

import numpy as np

from ..ops import buckets as shape_buckets
from ..ops.isocalc import IsotopePatternTable
from ..utils import tracing


class IonImageChunks:
    """The image export as the device hands it over: an iterator of
    ``(rows, n_pixels)`` f32 pieces of the flat ``(n_ions * K, n_pixels)``
    image block, in order, each but the last a multiple of 8 rows.

    ``shape`` is that of their concatenation as ``(n_ions, K, n_pixels)``,
    ``nbytes`` its size and ``n_chunks`` their number, all known before
    the first piece; ``nnz`` is the device's own count of non-zero pixels,
    set once the first piece is on the host (None where the export took
    more than one device call: those run one after the other, so the later
    counts come too late for a writer that needs the total first)."""

    def __init__(self, shape, n_chunks: int, pieces):
        self.shape, self.n_chunks, self.nnz = shape, n_chunks, None
        self.nbytes = 4 * int(np.prod(shape))
        self._pieces, self._held = pieces(self), []

    def wait_first(self) -> None:
        """Block until the first piece is on the host; the next ``next``
        hands it out."""
        if not self._held:
            self._held.append(next(self._pieces))

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        return self._held.pop() if self._held else next(self._pieces)


def iter_ion_images(backend, table: IsotopePatternTable) -> IonImageChunks:
    """``backend``'s export of ``table`` as a stream of row chunks.  ONE
    device program a call with the scatter done once
    (``JaxBackend._export_images``); its output is cut on the device into
    pieces of ``ops/buckets.export_chunk_rows`` flat rows, and every piece
    that holds a kept row is on the link before the first is waited for.
    The first call is dispatched here; a table above the batch takes
    further calls, one after the other (their footprint is the scoring
    batch's, proven to fit once, not twice).  Annotates the open span
    (``store_extract_images``) with the padded ``rows``, the
    ``fetched_bytes`` and the number of device ``calls``."""
    from .msm_basic import _slice_table

    b, n, k = backend.batch, table.n_ions, table.max_peaks
    n_pixels = backend.ds.n_pixels
    # batch internally: annotated subsets can exceed formula_batch
    tables = [table] if n <= b else [
        _slice_table(table, s, min(s + b, n)) for s in range(0, n, b)]
    rows = [shape_buckets.export_bucket(t.n_ions, b) for t in tables]
    per_chunk = shape_buckets.export_chunk_rows(backend._n_pix_b)
    # pieces that hold a kept row, per call: the rest of the bucket is
    # padding and never leaves the device
    kept = [-(-t.n_ions * k // per_chunk) for t in tables]
    tracing.annotate(
        rows=sum(rows), calls=len(tables),
        fetched_bytes=4 * backend._n_pix_b * sum(
            min(r * k, c * per_chunk) for r, c in zip(rows, kept)))
    ahead = [backend._export_images(tables[0])]

    def pieces(out: IonImageChunks):
        for t in tables:
            chunks, _b_x, nnz = ahead.pop() if ahead \
                else backend._export_images(t)
            left = t.n_ions * k
            while chunks:
                # a piece handed on is let go of here: its device buffer
                # and host copy live only as long as the consumer's view
                # smlint: host-sync-ok[image EXPORT; the annotated-subset fetch to host is the product of this function]
                host = np.asarray(chunks.pop(0))
                if nnz is not None and len(tables) == 1:
                    # smlint: host-sync-ok[the export's own (W,) count vector, landed with the first piece]
                    out.nnz, nnz = int(np.asarray(nnz)[:left].sum()), None
                # the kept rows and the real pixels of the bucket, in
                # place: no second host copy
                yield host[:left, :n_pixels]
                left -= host.shape[0]

    return IonImageChunks((n, k, n_pixels), sum(kept), pieces)

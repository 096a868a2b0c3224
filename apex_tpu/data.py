"""Host→device input pipeline (reference: the ``data_prefetcher`` class
in examples/imagenet/main_amp.py, which overlaps H2D copies with compute
on a side CUDA stream; SURVEY.md §1 L6).

TPU-native design: there are no user-managed streams — ``jax.device_put``
is asynchronous and XLA overlaps transfers with running computations by
itself.  What the prefetcher must supply is *pipelining depth*: issue the
next batch's transfer while the current step runs.  ``DevicePrefetcher``
keeps a ring of ``depth`` in-flight device batches fed from a background
host thread (so host-side batch construction — augmentation, decode,
numpy collation — also overlaps), which is the same two-deep pipeline the
reference builds with `stream.wait_stream` + `record_stream`.

Works with any iterator of pytrees (numpy or jax arrays).  When a
``sharding`` is given, batches land already laid out for the mesh
(`jax.device_put` with a NamedSharding performs the host-split +
multi-device transfer in one call).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Iterable, Iterator, Optional

import jax

from apex_tpu.telemetry.spans import span

_SENTINEL = object()


class DevicePrefetcher:
    """Iterate device-resident batches, ``depth`` transfers ahead.

    >>> with DevicePrefetcher(loader, depth=2) as pf:
    ...     for batch in pf:
    ...         state = step(state, batch)   # next H2D already in flight

    The reference's loop idiom ``input, target = prefetcher.next()``
    (returning None at exhaustion — repeatedly, like the apex example's
    data_prefetcher) is also supported for drop-in ports.  ``close()``
    (or the context manager) releases the feeder thread and its in-flight
    device batches on early exit.
    """

    def __init__(self, it: Iterable[Any], depth: int = 2,
                 sharding: Optional[Any] = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._src = iter(it)
        self._sharding = sharding
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(target=self._feed, daemon=True)
        self._thread.start()

    def _put_device(self, batch):
        if self._sharding is not None:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._sharding), batch)
        return jax.tree_util.tree_map(jax.device_put, batch)

    def _put_or_stop(self, item) -> bool:
        """Bounded put that aborts when close() is signalled; returns
        False if the prefetcher is shutting down."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _feed(self):
        try:
            for batch in self._src:
                if self._stop.is_set():
                    return
                if not self._put_or_stop(self._put_device(batch)):
                    return
        except BaseException as e:  # surfaced on the consumer side
            # the sentinel put below is the release barrier: __next__
            # reads _err only AFTER q.get() returns the sentinel, and
            # queue.Queue's internal lock orders the two
            self._err = e   # apexlint: disable=APX1001
        finally:
            self._put_or_stop(_SENTINEL)

    def __iter__(self) -> Iterator[Any]:
        return self

    def _publish_sentinel(self):
        """Best-effort sentinel publish so consumers blocked in q.get()
        wake; combined with __next__'s post-get _done check, a dropped
        publish (queue momentarily full) is still safe."""
        try:
            self._q.put_nowait(_SENTINEL)
        except queue.Full:
            pass

    def __next__(self):
        if self._done:
            raise StopIteration
        with span("apex/data/next"):    # the wait for a device batch
            item = self._q.get()
        if self._done and item is not _SENTINEL:
            # close() ran while we were blocked in get(): `item` is a
            # stale batch that slipped in after close()'s drain (the
            # feeder may have had one put in flight).  Shut down — and
            # re-publish so every other blocked consumer wakes too.
            self._publish_sentinel()
            raise StopIteration
        if item is _SENTINEL:
            self._done = True
            # re-publish for any OTHER consumer blocked in q.get() —
            # one sentinel must wake every waiter, not just the first
            self._publish_sentinel()
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        return item

    def next(self):
        """Reference-idiom alias: returns None at (and after) exhaustion
        instead of raising (matches data_prefetcher.next() in the apex
        example)."""
        try:
            return self.__next__()
        except StopIteration:
            return None

    def close(self):
        """Stop the feeder thread and drop queued device batches.  Safe
        to call more than once; called automatically by the context
        manager and on garbage collection."""
        self._done = True
        self._stop.set()
        while True:             # unblock a feeder stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        # re-publish the sentinel: a consumer already blocked in
        # __next__'s q.get() when close() ran would otherwise hang
        # forever (the drain above may have eaten the feeder's sentinel)
        self._publish_sentinel()
        self._thread.join(timeout=1.0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: don't leak the feeder thread
        try:
            self.close()
        except Exception:
            pass


def prefetch_to_device(it: Iterable[Any], depth: int = 2,
                       sharding: Optional[Any] = None):
    """Functional spelling of DevicePrefetcher (flax-utils-style name)."""
    return DevicePrefetcher(it, depth=depth, sharding=sharding)


def pack_sequences(sequences, max_len: int, pad_id: int = 0):
    """Pack variable-length token sequences into fixed (B, max_len)
    rows for segment-masked attention (the reference's fmha packed
    varlen contract — apex/contrib/fmha in SURVEY.md §2.3; here the
    flash kernel's ``segment_ids`` routing does the masking).

    First-fit-decreasing bin packing on the host (numpy).  Returns a
    dict of (B, max_len) int32 arrays:

    - ``tokens``: packed ids, ``pad_id`` in the tail of each row
    - ``segment_ids``: 1, 2, ... per packed sequence, 0 on padding —
      the unpacking key (and the downstream padding mask)
    - ``q_segment_ids`` / ``kv_segment_ids``: the attention form —
      pass ``(q_segment_ids, kv_segment_ids)`` to ``flash_attention``.
      Padding carries DISJOINT ids per side (-1 vs -2, the
      contrib.fmha convention), so pad rows are fully masked and
      output exact zeros; real segments never see padding or each
      other
    - ``positions``: 0-based position WITHIN each sequence (for RoPE /
      learned position lookups), 0 on padding

    Sequences longer than ``max_len`` raise — truncation policy is the
    caller's decision, not a packer default.
    """
    import numpy as np

    seqs = [np.asarray(s, dtype=np.int32).reshape(-1) for s in sequences]
    too_long = [i for i, s in enumerate(seqs) if len(s) > max_len]
    if too_long:
        raise ValueError(
            f"pack_sequences: sequence(s) {too_long[:5]} longer than "
            f"max_len={max_len}; truncate or split before packing")
    empty = [i for i, s in enumerate(seqs) if len(s) == 0]
    if empty:
        # an empty sequence would silently vanish from the packed
        # output and desync any caller zipping labels by input index
        raise ValueError(
            f"pack_sequences: sequence(s) {empty[:5]} are empty; "
            f"filter them out (and their labels) before packing")

    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i]))
    bins = []          # list of (free, [seq_idx, ...])
    for i in order:
        need = len(seqs[i])
        for b in bins:
            if b[0] >= need:
                b[0] -= need
                b[1].append(i)
                break
        else:
            bins.append([max_len - need, [i]])

    B = len(bins)
    tokens = np.full((B, max_len), pad_id, dtype=np.int32)
    segment_ids = np.zeros((B, max_len), dtype=np.int32)
    positions = np.zeros((B, max_len), dtype=np.int32)
    for r, (_, members) in enumerate(bins):
        off = 0
        for seg, i in enumerate(members, start=1):
            n = len(seqs[i])
            tokens[r, off:off + n] = seqs[i]
            segment_ids[r, off:off + n] = seg
            positions[r, off:off + n] = np.arange(n)
            off += n
    from apex_tpu.ops.attention import packed_segment_ids
    q_ids, kv_ids = packed_segment_ids(segment_ids, xp=np)
    return {"tokens": tokens, "segment_ids": segment_ids,
            "positions": positions,
            "q_segment_ids": q_ids, "kv_segment_ids": kv_ids}


def pack_dataset(sequences, max_len: int, rows_per_batch: int,
                 pad_id: int = 0, buffer_batches: int = 8):
    """Stream packed batches from an iterable of token sequences.

    Buffers ``rows_per_batch * buffer_batches`` sequences, packs the
    buffer with :func:`pack_sequences` (FFD packs best with many
    candidates), and yields dicts shaped exactly like its output but
    with EXACTLY ``rows_per_batch`` rows per batch — fixed shapes, so
    one jit compilation serves the whole stream and the result feeds
    :class:`DevicePrefetcher` directly::

        batches = pack_dataset(corpus_iter, max_len=2048,
                               rows_per_batch=8)
        for batch in prefetch_to_device(batches, depth=2):
            step(params, batch["tokens"], batch["segment_ids"], ...)

    Rows left over when a buffer doesn't fill a whole batch are
    unpacked back into the carry (no mid-stream padding waste); only
    the stream's FINAL partial batch is padded with all-padding rows
    (segment 0 everywhere — downstream loss masking by
    ``segment_ids == 0`` already ignores them).  Sequences longer than
    ``max_len`` or empty raise, as in pack_sequences.
    """
    import numpy as np

    from apex_tpu.ops.attention import packed_segment_ids

    # pad-row fills: segment 0 + the q/kv ids the single-home helper
    # assigns to padding (never hardcode the -1/-2 convention here)
    _qpad, _kvpad = packed_segment_ids(np.zeros((), np.int32), xp=np)
    pad_fill = {"tokens": pad_id, "segment_ids": 0, "positions": 0,
                "q_segment_ids": int(_qpad), "kv_segment_ids": int(_kvpad)}

    def chunks(buf, final):
        """Yield full batches; return leftover sequences (or pad out
        the last batch when final)."""
        packed = pack_sequences(buf, max_len, pad_id=pad_id)
        rows = packed["tokens"].shape[0]
        full = rows - rows % rows_per_batch
        for start in range(0, full, rows_per_batch):
            yield {k: v[start:start + rows_per_batch]
                   for k, v in packed.items()}
        leftover = []
        if rows != full:
            tail = {k: v[full:] for k, v in packed.items()}
            if final:
                short = rows_per_batch - tail["tokens"].shape[0]
                yield {k: np.concatenate(
                    [v, np.full((short, max_len), pad_fill[k],
                                dtype=v.dtype)], axis=0)
                    for k, v in tail.items()}
            else:
                segs, toks = tail["segment_ids"], tail["tokens"]
                for r in range(toks.shape[0]):
                    for seg in range(1, int(segs[r].max()) + 1):
                        leftover.append(toks[r][segs[r] == seg])
        return leftover

    # flush by TOKEN count, not sequence count: tokens >= threshold
    # guarantees >= rows_per_batch * buffer_batches bins, so at least
    # one FULL batch is emitted per flush and the carry always shrinks
    # below a batch's worth (sequence-count flushing degraded to a
    # full repack per input sequence for short sequences)
    buf, toks = [], 0
    threshold = rows_per_batch * buffer_batches * max_len
    for s in sequences:
        buf.append(s)
        toks += len(s)
        if toks >= threshold:
            buf = yield from chunks(buf, final=False)
            toks = sum(len(x) for x in buf)
    if buf:
        yield from chunks(buf, final=True)

"""Resident pk-plane caches: host row cache, device LRU, batch memo —
and the per-device shards of the mesh layout.

Committee PUBKEYS recur period after period (registered keys are
stable until release) while signatures are fresh every vote — so the
G2 half of the audit's marshalling cost, the largest, is cacheable at
three levels:

- **host row cache** (`_pk_rows_to_limbs`): removes the limb
  CONVERSION from a warm audit (FIFO, `_PK_ROW_CACHE_MAX` rows);
- **device-resident LRU** (`GETHSHARDING_TPU_RESIDENT`, default on):
  removes the TRANSFER — per-row device buffers keyed by
  (pk_row_key, width, wire) under a memory-accounted LRU bounded by
  ``GETHSHARDING_TPU_RESIDENT_MB``;
- **batch memo**: the steady-state audit repeats the SAME row-key
  tuple every period, so the stacked kernel planes are reused whole —
  zero transfers AND zero per-dispatch device stacking ops.

Under `GETHSHARDING_PRECOMP` a fourth resident kind joins the SAME
byte-budgeted device LRU: per-row Miller line-coefficient TABLES
(`(key, "lines")` entries, `ops/bn256_jax.precompute_lines` output).
A cold row pays one precompute dispatch; every warm audit then ships
zero G2 bytes AND skips the fixed-argument point arithmetic entirely.
The miss rows of a dispatch are precomputed together, padded to
`marshal.bucket_size` of their number, so that the precompute is one
compiled program per bucket, counted and settled like every other
(`g2_line_precompute` in `jax/compile_cache/*` and the compile watch).
That program hands back one table a row, and a second one
(`line_table_stack`, one per dispatch bucket) stacks a dispatch's
tables: between the miss planes' staging and the verify's launch no
eager operation touches a table.
Tables are keyed by `pk_row_key` alone — the on-device aggregate is a
function of row content only, so one table serves every committee
width and wire dtype. Entries are charged at their TRUE device byte
count (int32 tables even under the u16 wire — a plane-shape estimate
would under-charge ~2x and trip devscope's claimed-vs-census drift
gate).

On a mesh layout the device LRU becomes PER-DEVICE SHARDS
(`MeshCacheShard`): each mesh slot owns an independent LRU holding
only the rows its slab consumes, with its own byte budget (an equal
split of the resident budget), its own hit/miss/evict counters and
HBM gauge (``jax/pk_device_cache/shard<i>/*``), and its own devscope
census owner (``pk_plane_lru_shard<i>``) — so the census attributes
every resident byte to the device that actually holds it, and the
owners are disjoint by construction.

`ResidentPkCache` is mixed into `JaxSigBackend` (dispatch.py): state
lives on the backend instance under the SAME attribute names the
pre-split backend used, so the residency tests and the devscope
census cross-check compose unchanged.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.sigbackend import marshal

# the two parts of sig/transfer_time that only a line-table miss pays
# (a batch-memo hit enters neither)
_T_LINE_PRECOMPUTE = metrics.timer("sig/line_precompute_time")
_T_LINE_STACK = metrics.timer("sig/line_stack_time")


class MeshCacheShard:
    """One device's slice of the resident pk plane: its own LRU, byte
    budget, gauges and devscope census owner (registered by the
    mixin). All mutation happens under the owning backend's mesh lock;
    the shard itself is a dumb record."""

    __slots__ = ("index", "device", "budget", "cache", "bytes",
                 "zero_rows", "m_hit", "m_miss", "m_evict", "g_bytes")

    def __init__(self, index: int, device, budget: int):
        self.index = index
        self.device = device
        self.budget = budget
        self.cache: OrderedDict = OrderedDict()
        self.bytes = 0
        self.zero_rows: dict = {}  # (width, wire) -> device zero planes
        prefix = f"jax/pk_device_cache/shard{index}"
        self.m_hit = metrics.counter(prefix + "/hits")
        self.m_miss = metrics.counter(prefix + "/misses")
        self.m_evict = metrics.counter(prefix + "/evictions")
        self.g_bytes = metrics.gauge(prefix + "/bytes")


class ResidentPkCache:
    """The cache half of `JaxSigBackend` (a mixin: state lands on the
    backend instance so existing attribute contracts hold)."""

    # rows; an entry holds BOTH coordinate arrays: ~54 KB at 135x(2,25)
    # int32, so 1024 rows cap the cache near 55 MB (production needs at
    # most one row per shard in the steady state)
    _PK_ROW_CACHE_MAX = 1024

    _pk_batch_memo_nbytes = 0
    _pk_line_memo_nbytes = 0
    # class default so census callbacks work on a backend whose
    # __init__ predates the line memo (devscope's partial-construction
    # registration path)
    _pk_line_memo = None

    def _init_pk_caches(self) -> None:
        """Construct the cache state (called from the backend's
        __init__; the backend is a process-wide singleton shared by
        every actor thread, so every structure is lock-guarded)."""
        self._pk_row_cache: dict = {}
        self._pk_row_lock = threading.Lock()
        self._resident = os.environ.get(
            "GETHSHARDING_TPU_RESIDENT", "1") != "0"
        self._resident_budget = int(float(os.environ.get(
            "GETHSHARDING_TPU_RESIDENT_MB", "256")) * (1 << 20))
        self._pk_dev_cache: OrderedDict = OrderedDict()
        self._pk_dev_bytes = 0
        self._pk_dev_lock = threading.Lock()
        self._pk_batch_memo: "tuple | None" = None  # (key, planes, nbytes)
        self._pk_line_memo: "tuple | None" = None  # (key, (tab, inf), bytes)
        self._pk_zero_rows: dict = {}  # width -> device zero row planes
        self._m_row_hit = metrics.counter("jax/pk_row_cache/hits")
        self._m_row_miss = metrics.counter("jax/pk_row_cache/misses")
        self._m_dev_hit = metrics.counter("jax/pk_device_cache/hits")
        self._m_dev_miss = metrics.counter("jax/pk_device_cache/misses")
        self._m_dev_evict = metrics.counter("jax/pk_device_cache/evictions")
        self._g_dev_bytes = metrics.gauge("jax/pk_device_cache/bytes")
        # mesh state (filled by _init_mesh_shards on mesh layouts)
        self._mesh_shards: list = []
        self._mesh_memo: "tuple | None" = None
        self._mesh_line_memo: "tuple | None" = None
        self._mesh_lock = threading.Lock()

    def _register_census_owner(self) -> None:
        """Register the resident plane as a devscope census owner so
        the poller can cross-check the cache's OWN byte accounting
        against what the device actually holds (drift beyond tolerance
        -> devscope/mem/drift). The registration holds a WEAK ref: the
        owner registry is module-global and must not pin a discarded
        backend (and its device LRU) alive; a dead ref reads as an
        empty owner. Latest instance wins the name — the registry
        backend is a process singleton (get_backend cache), so
        replacement only happens in tests building instances
        directly."""
        import weakref

        from gethsharding_tpu import devscope

        self_ref = weakref.ref(self)

        def _claimed() -> int:
            backend = self_ref()
            return (0 if backend is None
                    else backend._resident_claimed_bytes())

        def _buffers() -> list:
            backend = self_ref()
            return [] if backend is None else backend._resident_buffers()

        devscope.register_owner("pk_plane_lru", claimed_fn=_claimed,
                                buffers_fn=_buffers)

    def _resident_claimed_bytes(self) -> int:
        """The resident plane's own accounting — the number the
        devscope census is cross-checked against. Covers exactly what
        `_resident_buffers` censuses: cache entries + batch memo +
        the shared zero rows (never evicted, outside the LRU budget —
        counting them on one side only would read as permanent
        drift)."""
        zero = sum(int(b.nbytes)
                   for row in self._pk_zero_rows.copy().values()
                   for b in row)
        zero += sum(int(b.nbytes) for b in self._fixed_tables())
        with self._pk_dev_lock:
            return (self._pk_dev_bytes + self._pk_batch_memo_nbytes
                    + self._pk_line_memo_nbytes + zero)

    def _resident_buffers(self) -> list:
        """Every device buffer the resident plane holds (cache rows,
        the batch/line memos, the shared zero rows, the resident
        generator line table) for census attribution."""
        out: list = []
        with self._pk_dev_lock:
            for entry in self._pk_dev_cache.values():
                # line-table entries pad slot 2 with None (no third
                # buffer) to keep the pk-plane entry shape
                out.extend(b for b in entry[:3] if b is not None)
            memo = self._pk_batch_memo
            line_memo = self._pk_line_memo
        if memo is not None:
            out.extend(memo[1])
        if line_memo is not None:
            out.extend(line_memo[1])
        # .copy(): atomic snapshot — _zero_pk_row publishes new rows
        # without the dev lock, and a mid-iteration insert would raise
        for row in self._pk_zero_rows.copy().values():
            out.extend(row)
        out.extend(self._fixed_tables())
        return out

    def _fixed_tables(self) -> list:
        """The resident tables of fixed points, outside the LRU: the
        generator line table and the SRS's fixed-base MSM tables, once
        built (single-device layouts)."""
        out = []
        gen = getattr(self, "_gen_lines_dev", None)
        if gen is not None:
            out.append(gen)
        srs = getattr(self, "_srs_tabs", None)
        if srs is not None and not getattr(self, "_mesh_shards", None):
            out.extend(srs[1])
        return out

    # -- pubkey-row limb cache (host) --------------------------------------
    # Caching is per ROW keyed by caller-supplied hashable keys (the
    # notary passes the wire hex strings, whose hashes python interns):
    # per-POINT value keys were tried and the 13k bigint-tuple hashes
    # per audit cost as much as the conversion they saved.

    def _pk_rows_to_limbs(self, rows, width: int, row_keys=None):
        import numpy as np

        if row_keys is None:
            return self._bn.g2_committee_to_limbs(rows, width)
        cache = self._pk_row_cache
        nl = int(np.asarray(self._bn.FP.one).shape[-1])
        B = len(rows)
        # under the u16 wire the pk planes — the audit's largest buffers
        # — are assembled (and cached) as uint16 at MISS time, so cache
        # hits skip the narrowing copy entirely (limbs are 12-bit)
        dtype = np.uint16 if self._wire_u16 else np.int32
        xs = np.zeros((B, width, 2, nl), dtype)
        ys = np.zeros((B, width, 2, nl), dtype)
        mask = np.zeros((B, width), bool)
        misses = []  # (b, key, row) — bulk-converted in ONE pass below
        hits = 0
        for b, row in enumerate(rows):
            if len(row) > width:
                raise ValueError(
                    f"committee of {len(row)} exceeds width {width}")
            if not row:
                continue
            key = row_keys[b] if b < len(row_keys) else None
            if key is None:
                entry = None
            else:
                with self._pk_row_lock:
                    entry = cache.get(key)
            if entry is None:
                misses.append((b, key, row))
                continue
            hits += 1
            k = entry[0].shape[0]
            xs[b, :k], ys[b, :k], mask[b, :k] = entry
        self._m_row_hit.inc(hits)
        self._m_row_miss.inc(sum(1 for _, key, _ in misses
                                 if key is not None))
        if misses:
            # one bulk bit-plane conversion for every miss row (a cold
            # audit would otherwise pay the fixed numpy overhead per
            # row), emitted straight into the wire dtype
            miss_w = max(len(row) for _, _, row in misses)
            mx, my, mm = self._bn.g2_committee_to_limbs(
                [row for _, _, row in misses], miss_w, out_dtype=dtype)
            for i, (b, key, row) in enumerate(misses):
                k = len(row)
                xs[b, :k] = mx[i, :k]
                ys[b, :k] = my[i, :k]
                mask[b, :k] = mm[i, :k]
                if key is not None:
                    with self._pk_row_lock:
                        while len(cache) >= self._PK_ROW_CACHE_MAX:
                            # FIFO: evict one stale row, not all of them
                            cache.pop(next(iter(cache)))
                        # copies, not views: a view would pin the whole
                        # bulk conversion array per cached row (astype
                        # copies even at the same dtype)
                        cache[key] = (mx[i, :k].astype(dtype),
                                      my[i, :k].astype(dtype),
                                      mm[i, :k].copy())
        return xs, ys, mask

    # -- device-resident pk planes (single-device LRU) ---------------------

    def _pk_resident_resolve(self, st: dict, rows, keys) -> None:
        """Host half of the resident path: claim device-cache hits,
        bulk-marshal miss rows (through the host row cache). A pointful
        row without a key is uncacheable — transferred every dispatch;
        an empty row maps to the shared on-device zero planes."""
        width, wire = st["width"], self._wire
        # the batch memo is only sound when every pointful row is keyed
        # (a keyless row's contents are not determined by the key tuple)
        if all(k is not None or not row for row, k in zip(rows, keys)):
            batch_key = (tuple(keys), st["bucket"], width, wire)
        else:
            batch_key = None
        st["batch_key"] = batch_key
        with self._pk_dev_lock:
            memo = self._pk_batch_memo
        if batch_key is not None and memo is not None \
                and memo[0] == batch_key:
            st["memo_planes"] = memo[1]
            st["hit_rows"] = st["pk_rows"]
            st["hit_bytes"] = memo[2]
            st["miss_planes"] = None
            self._m_dev_hit.inc(st["pk_rows"])
            return
        st["memo_planes"] = None
        plan = []  # per row: ("zero",) | ("hit", entry) | ("miss", j)
        misses = []  # (row, key)
        hit_rows = hit_bytes = 0
        with self._pk_dev_lock:
            cache = self._pk_dev_cache
            for row, key in zip(rows, keys):
                if not row:
                    plan.append(("zero",))
                    continue
                entry = None
                if key is not None:
                    entry = cache.get((key, width, wire))
                    if entry is not None:
                        cache.move_to_end((key, width, wire))
                if entry is not None:
                    plan.append(("hit", entry))
                    hit_rows += 1
                    hit_bytes += entry[3]
                else:
                    plan.append(("miss", len(misses)))
                    misses.append((row, key))
        self._m_dev_hit.inc(hit_rows)
        self._m_dev_miss.inc(len(misses))
        st["plan"] = plan
        st["hit_rows"], st["hit_bytes"] = hit_rows, hit_bytes
        if misses:
            # bulk conversion at the dispatch width, through the HOST
            # row cache: a device-evicted row re-transfers but does not
            # re-pay the bit-plane conversion
            mx, my, mm = self._pk_rows_to_limbs(
                [row for row, _ in misses], width,
                row_keys=[key for _, key in misses])
            st["miss_planes"] = (mx, my, mm)
            st["miss_keys"] = [key for _, key in misses]
        else:
            st["miss_planes"] = None

    def _pk_resident_planes(self, st: dict):
        """Device half: ship miss rows, stack hits + misses + zeros into
        the (B, width, 2, nl) kernel planes. Returns (px, py, pm,
        transferred_g2_bytes)."""
        jnp = self._jnp
        if st["memo_planes"] is not None:
            px, py, pm = st["memo_planes"]
            return px, py, pm, 0

        miss_dev = []
        g2_bytes = 0
        if st["miss_planes"] is not None:
            mx, my, mm = st["miss_planes"]
            if st["check"] and self._wire_u16 and mx.size:
                # the u16 invariant, pinned once per row AT SHIP TIME
                # (hit rows were checked when first transferred)
                marshal.assert_canonical_limbs(mx, my)
            # ONE bulk transfer for ALL miss rows (the planes are already
            # contiguous); the cache entries are per-row device slices —
            # device-side ops, not M separate host->device round trips
            dmx, dmy, dmm = (jnp.asarray(mx), jnp.asarray(my),
                             jnp.asarray(mm))
            g2_bytes = mx.nbytes + my.nbytes + mm.nbytes
            for j, key in enumerate(st["miss_keys"]):
                nbytes = mx[j].nbytes + my[j].nbytes + mm[j].nbytes
                entry = (dmx[j], dmy[j], dmm[j], nbytes)
                if key is not None:
                    self._pk_dev_insert(
                        (key, st["width"], self._wire), entry)
                miss_dev.append(entry)
        zx, zy, zm = self._zero_pk_row(st["width"])
        xs, ys, ms = [], [], []
        for step in st["plan"]:
            if step[0] == "zero":
                entry = (zx, zy, zm)
            elif step[0] == "hit":
                entry = step[1]
            else:
                entry = miss_dev[step[1]]
            xs.append(entry[0])
            ys.append(entry[1])
            ms.append(entry[2])
        # device-side assembly: concatenation of resident buffers, no
        # host bytes on the link
        px, py, pm = jnp.stack(xs), jnp.stack(ys), jnp.stack(ms)
        if st["batch_key"] is not None:
            # memoize the assembled batch; its hit ledger is what THIS
            # assembly would have cost over the wire
            self._set_batch_memo(st["batch_key"], (px, py, pm),
                                 st["hit_bytes"] + g2_bytes)
        return px, py, pm, g2_bytes

    def _pk_dev_insert(self, key, entry) -> None:
        """LRU insert with byte-accounted eviction (gauge + counter)."""
        with self._pk_dev_lock:
            cache = self._pk_dev_cache
            if key in cache:
                cache.move_to_end(key)
                return
            cache[key] = entry
            self._pk_dev_bytes += entry[3]
            while self._pk_dev_bytes > self._resident_budget and cache:
                _, old = cache.popitem(last=False)
                self._pk_dev_bytes -= old[3]
                self._m_dev_evict.inc()
            self._g_dev_bytes.set(
                self._pk_dev_bytes + self._pk_batch_memo_nbytes
                + self._pk_line_memo_nbytes)

    def _set_batch_memo(self, key, planes, hit_bytes) -> None:
        px, py, pm = planes
        with self._pk_dev_lock:
            self._pk_batch_memo = (key, planes, hit_bytes)
            self._pk_batch_memo_nbytes = px.nbytes + py.nbytes + pm.nbytes
            self._g_dev_bytes.set(
                self._pk_dev_bytes + self._pk_batch_memo_nbytes
                + self._pk_line_memo_nbytes)

    def _zero_pk_row(self, width: int):
        """Shared on-device zero planes for empty/padded rows (mask all
        False -> the kernel rejects the row, scalar parity) — created
        once per (width, wire), never transferred per dispatch."""
        import numpy as np

        key = (width, self._wire)
        row = self._pk_zero_rows.get(key)
        if row is None:
            jnp = self._jnp
            nl = int(np.asarray(self._bn.FP.one).shape[-1])
            dtype = np.uint16 if self._wire_u16 else np.int32
            row = (jnp.zeros((width, 2, nl), dtype),
                   jnp.zeros((width, 2, nl), dtype),
                   jnp.zeros((width,), bool))
            self._pk_zero_rows[key] = row
        return row

    # -- device-resident line tables (fixed-base precomp) ------------------
    # The precompute path's residents: per pk_row_key the dense Miller
    # line-coefficient table (L, 3, 2, nl) int32 + its infinity flag,
    # sharing the pk-plane LRU (one byte budget, one eviction order).
    # Entries are (table, inf, None, nbytes): the None pads to the
    # pk-plane entry shape so the census walks both kinds; nbytes is the
    # TRUE device byte count of the int32 table (under the u16 wire a
    # plane-shape estimate would under-charge ~2x and trip the devscope
    # claimed-vs-census drift gate). Tables are keyed `(key, "lines")` —
    # content only: the aggregate is width/wire-independent as a GROUP
    # element, so verdicts are exact for any consumer; the projective
    # REPRESENTATIVE (and hence raw f bits) matches the recompute path
    # when the table was built at the same dispatch width.

    def _zero_line_row(self):
        """Shared on-device zero line table for empty rows: inf=True ->
        the precomp kernel rejects the row, matching the recompute
        kernel's `fp2_is_zero(pZ)` rejection (scalar parity)."""
        import numpy as np

        row = self._pk_zero_rows.get("lines")
        if row is None:
            jnp = self._jnp
            # staged from the host: no eager operation inside the stage
            row = (jnp.asarray(np.zeros(self._bn.LINE_TABLE_SHAPE,
                                        np.int32)),
                   jnp.asarray(np.asarray(True)))
            self._pk_zero_rows["lines"] = row
        return row

    def _line_resolve(self, st: dict, rows, keys) -> None:
        """Host half of the precomp path: claim line-table hits, plan
        misses (whose pk planes alone are marshalled — hit rows ship
        NOTHING, not even the pk plane the recompute path would need)."""
        width = st["width"]
        if all(k is not None or not row for row, k in zip(rows, keys)):
            batch_key = (tuple(keys), st["bucket"], "precomp")
        else:
            batch_key = None
        st["line_key"] = batch_key
        with self._pk_dev_lock:
            memo = self._pk_line_memo
        if batch_key is not None and memo is not None \
                and memo[0] == batch_key:
            st["line_memo"] = memo[1]
            st["hit_rows"] = st["pk_rows"]
            st["hit_bytes"] = memo[2]
            st["line_miss"] = None
            self._m_dev_hit.inc(st["pk_rows"])
            return
        st["line_memo"] = None
        plan = []  # per row: ("zero",) | ("hit", entry) | ("miss", j)
        misses = []  # (row, key)
        hit_rows = hit_bytes = 0
        with self._pk_dev_lock:
            cache = self._pk_dev_cache
            for row, key in zip(rows, keys):
                if not row:
                    plan.append(("zero",))
                    continue
                entry = None
                if key is not None:
                    entry = cache.get((key, "lines"))
                    if entry is not None:
                        cache.move_to_end((key, "lines"))
                if entry is not None:
                    plan.append(("hit", entry))
                    hit_rows += 1
                    hit_bytes += entry[3]
                else:
                    plan.append(("miss", len(misses)))
                    misses.append((row, key))
        self._m_dev_hit.inc(hit_rows)
        self._m_dev_miss.inc(len(misses))
        st["line_plan"] = plan
        st["hit_rows"], st["hit_bytes"] = hit_rows, hit_bytes
        if misses:
            st["line_miss"] = self._miss_planes(misses, width)
            st["line_miss_keys"] = [key for _, key in misses]
        else:
            st["line_miss"] = None

    def _miss_planes(self, misses, width: int):
        """The host pk planes of a precompute's miss rows, padded with
        empty rows (mask all False) to `bucket_size(len(misses))`: the
        precompute compiles once per bucket, not once per miss count."""
        pad = marshal.bucket_size(len(misses)) - len(misses)
        return self._pk_rows_to_limbs(
            [row for row, _ in misses] + [[]] * pad, width,
            row_keys=[key for _, key in misses])

    def _counted_launch(self, op: str, shape: tuple, fn, *args):
        """Launch a jitted program of the line-table path. A shape this
        process has not launched before is a compile like any other:
        counted by `_note_shape`, booked and settled by
        `compile_span`, held by shape by `_run`."""
        fresh = self._note_shape(op, *shape)
        with self._compiles.compile_span(op, shape, fresh) as booking:
            return self._run(op, shape, fn, args, booking)

    def _precompute_lines(self, planes, width: int, *shape_tail):
        """The precompute over padded miss planes already on their
        device, one program per bucket: returns a table and a flag A
        ROW, cut inside the program, the bucket's empty rows too."""
        shape = (int(planes[0].shape[0]), width, self._wire) + shape_tail
        return self._counted_launch("g2_line_precompute", shape,
                                    self._precompute, *planes)

    def _line_entries(self, tabs, infs, n: int) -> list:
        """The LRU entries of a precompute's first `n` rows (the tables
        of the bucket's empty rows are dropped). `nbytes` is the true
        device byte count of an int32 table and its bool flag, from the
        kernel's shape and dtypes: the same for every key."""
        import math

        import numpy as np

        nbytes = (math.prod(self._bn.LINE_TABLE_SHAPE)
                  * np.dtype(np.int32).itemsize + np.dtype(bool).itemsize)
        return [(tabs[j], infs[j], None, nbytes) for j in range(n)]

    def _stack_line_plan(self, plan, miss_dev, zero, *shape_tail):
        """One (table, flag) a row of `plan` (zero row, hit, miss),
        stacked into the (B, L, 3, 2, nl) table plane and the (B,)
        infinity flags by ONE launch of 2 x B arguments, one program per
        B: an eager `jnp.stack` is two dispatches a row."""
        ts, fs = [], []
        for step in plan:
            if step[0] == "zero":
                entry = zero
            elif step[0] == "hit":
                entry = step[1]
            else:
                entry = miss_dev[step[1]]
            ts.append(entry[0])
            fs.append(entry[1])
        return self._counted_launch(
            "line_table_stack", (len(ts),) + shape_tail,
            self._stack_lines, ts, fs)

    def _line_tables(self, st: dict):
        """Device half of the precomp path: ONE precompute dispatch
        walks the fixed-argument point arithmetic for ALL miss rows
        (cold cost, paid once per key) and hands back one table a row,
        then ONE stack dispatch assembles hits + misses + zeros into
        the (B, L, 3, 2, nl) table plane + (B,) infinity flags. No
        eager operation touches a table. Returns (table, inf,
        transferred_g2_bytes)."""
        jnp = self._jnp
        if st["line_memo"] is not None:
            tab, inf = st["line_memo"]
            return tab, inf, 0
        miss_dev = []
        g2_bytes = 0
        if st["line_miss"] is not None:
            with tracing.stage("sig/line_precompute_time",
                               _T_LINE_PRECOMPUTE):
                mx, my, mm = st["line_miss"]
                if st["check"] and self._wire_u16 and mx.size:
                    marshal.assert_canonical_limbs(mx, my)
                # what crosses the link, the bucket's empty rows too
                g2_bytes = mx.nbytes + my.nbytes + mm.nbytes
                tabs, infs = self._precompute_lines(
                    (jnp.asarray(mx), jnp.asarray(my), jnp.asarray(mm)),
                    st["width"])
                keys = st["line_miss_keys"]
                miss_dev = self._line_entries(tabs, infs, len(keys))
                for key, entry in zip(keys, miss_dev):
                    if key is not None:
                        self._pk_dev_insert((key, "lines"), entry)
        with tracing.stage("sig/line_stack_time", _T_LINE_STACK):
            tab, inf = self._stack_line_plan(
                st["line_plan"], miss_dev, self._zero_line_row())
            if st["line_key"] is not None:
                with self._pk_dev_lock:
                    self._pk_line_memo = (st["line_key"], (tab, inf),
                                          st["hit_bytes"] + g2_bytes)
                    self._pk_line_memo_nbytes = (int(tab.nbytes)
                                                 + int(inf.nbytes))
                    self._g_dev_bytes.set(
                        self._pk_dev_bytes + self._pk_batch_memo_nbytes
                        + self._pk_line_memo_nbytes)
        return tab, inf, g2_bytes

    # -- per-device mesh shards --------------------------------------------

    def _init_mesh_shards(self, layout) -> None:
        """One `MeshCacheShard` per mesh slot: an equal split of the
        resident byte budget, per-shard gauges, and a per-shard
        devscope census owner (disjoint by construction: a shard holds
        only buffers committed to ITS device)."""
        import weakref

        from gethsharding_tpu import devscope

        per_device = max(1, self._resident_budget // layout.n_devices)
        self._mesh_shards = [MeshCacheShard(i, dev, per_device)
                             for i, dev in enumerate(layout.devices)]
        self_ref = weakref.ref(self)
        for shard in self._mesh_shards:
            idx = shard.index

            def _claimed(idx=idx) -> int:
                backend = self_ref()
                return (0 if backend is None
                        else backend._mesh_claimed_bytes(idx))

            def _buffers(idx=idx) -> list:
                backend = self_ref()
                return ([] if backend is None
                        else backend._mesh_shard_buffers(idx))

            devscope.register_owner(f"pk_plane_lru_shard{idx}",
                                    claimed_fn=_claimed,
                                    buffers_fn=_buffers)

    def _mesh_claimed_bytes(self, idx: int) -> int:
        """Shard `idx`'s own accounting: its LRU bytes + its zero rows
        + its equal slice of the (leading-axis-sharded) batch memo."""
        shard = self._mesh_shards[idx]
        with self._mesh_lock:
            total = shard.bytes
            memo = self._mesh_memo
            line_memo = self._mesh_line_memo
            zero = sum(int(b.nbytes)
                       for row in shard.zero_rows.values() for b in row)
        total += zero
        if memo is not None:
            total += memo[3] // max(1, len(self._mesh_shards))
        if line_memo is not None:
            total += line_memo[3] // max(1, len(self._mesh_shards))
        return total

    def _mesh_shard_buffers(self, idx: int) -> list:
        """Every device buffer shard `idx` holds — its LRU entries and
        zero rows, plus this device's addressable slice of the memoized
        global planes — for census attribution."""
        shard = self._mesh_shards[idx]
        out: list = []
        with self._mesh_lock:
            for entry in shard.cache.values():
                out.extend(b for b in entry[:3] if b is not None)
            memo = self._mesh_memo
            line_memo = self._mesh_line_memo
            zero_rows = list(shard.zero_rows.values())
        for row in zero_rows:
            out.extend(row)
        for m in (memo, line_memo):
            if m is not None:
                for arr in m[1]:
                    for piece in arr.addressable_shards:
                        if piece.device == shard.device:
                            out.append(piece.data)
        return out

    def _mesh_zero_row(self, shard: MeshCacheShard, width: int):
        """Shard-local zero planes (the `_zero_pk_row` contract, but
        committed to the shard's device so the per-device stack stays
        on-device)."""
        import numpy as np

        key = (width, self._wire)
        with self._mesh_lock:
            row = shard.zero_rows.get(key)
        if row is None:
            import jax

            nl = int(np.asarray(self._bn.FP.one).shape[-1])
            dtype = np.uint16 if self._wire_u16 else np.int32
            row = tuple(
                jax.device_put(z, shard.device)
                for z in (np.zeros((width, 2, nl), dtype),
                          np.zeros((width, 2, nl), dtype),
                          np.zeros((width,), bool)))
            with self._mesh_lock:
                shard.zero_rows.setdefault(key, row)
                row = shard.zero_rows[key]
        return row

    def _mesh_shard_insert(self, shard: MeshCacheShard, key,
                           entry) -> None:
        """Per-shard LRU insert with byte-accounted eviction: the
        shard's counters AND the process-wide eviction counter tick, so
        single-device dashboards keep reading."""
        with self._mesh_lock:
            cache = shard.cache
            if key in cache:
                cache.move_to_end(key)
                return
            cache[key] = entry
            shard.bytes += entry[3]
            while shard.bytes > shard.budget and cache:
                _, old = cache.popitem(last=False)
                shard.bytes -= old[3]
                shard.m_evict.inc()
                self._m_dev_evict.inc()
            shard.g_bytes.set(shard.bytes)

    def _mesh_pk_planes(self, st: dict, rows, keys, layout):
        """The mesh resident path: resolve every (padded) batch row
        against ITS device's cache shard, ship misses only to their
        owning device, stack per-device slabs on-device and assemble
        the global `NamedSharding(P('shard'))` planes with zero
        cross-device traffic. Returns (px, py, pm, transferred
        g2_bytes); fills st["hit_rows"/"hit_bytes"/"batch_key"]."""
        import jax

        jnp = self._jnp
        width, wire, bucket = st["width"], self._wire, st["bucket"]
        rpd = layout.rows_per_device(bucket)
        if keys is not None and all(
                k is not None or not row for row, k in zip(rows, keys)):
            batch_key = (tuple(keys), bucket, width, wire,
                         layout.n_devices)
        else:
            batch_key = None
        st["batch_key"] = batch_key
        with self._mesh_lock:
            memo = self._mesh_memo
        if batch_key is not None and memo is not None \
                and memo[0] == batch_key:
            px, py, pm = memo[1]
            st["hit_rows"] = st["pk_rows"]
            st["hit_bytes"] = memo[2]
            self._m_dev_hit.inc(st["pk_rows"])
            return px, py, pm, 0

        per_x, per_y, per_m = [], [], []
        g2_bytes = hit_rows = hit_bytes = miss_rows = 0
        for shard in self._mesh_shards:
            lo = shard.index * rpd
            s_rows = rows[lo:lo + rpd]
            s_keys = (keys[lo:lo + rpd] if keys is not None
                      else [None] * len(s_rows))
            plan = []  # ("zero",) | ("hit", entry) | ("miss", j)
            misses = []  # (row, key)
            with self._mesh_lock:
                for row, key in zip(s_rows, s_keys):
                    if not row:
                        plan.append(("zero",))
                        continue
                    entry = None
                    if key is not None:
                        entry = shard.cache.get((key, width, wire))
                        if entry is not None:
                            shard.cache.move_to_end((key, width, wire))
                    if entry is not None:
                        plan.append(("hit", entry))
                        hit_rows += 1
                        hit_bytes += entry[3]
                        shard.m_hit.inc()
                    else:
                        plan.append(("miss", len(misses)))
                        misses.append((row, key))
                        shard.m_miss.inc()
            miss_dev = []
            if misses:
                # bulk conversion through the HOST row cache, then ONE
                # transfer to THIS shard's device only
                mx, my, mm = self._pk_rows_to_limbs(
                    [row for row, _ in misses], width,
                    row_keys=[key for _, key in misses])
                if st["check"] and self._wire_u16 and mx.size:
                    marshal.assert_canonical_limbs(mx, my)
                dmx = jax.device_put(mx, shard.device)
                dmy = jax.device_put(my, shard.device)
                dmm = jax.device_put(mm, shard.device)
                g2_bytes += mx.nbytes + my.nbytes + mm.nbytes
                miss_rows += len(misses)
                for j, (row, key) in enumerate(misses):
                    nbytes = mx[j].nbytes + my[j].nbytes + mm[j].nbytes
                    entry = (dmx[j], dmy[j], dmm[j], nbytes)
                    if key is not None:
                        self._mesh_shard_insert(
                            shard, (key, width, wire), entry)
                    miss_dev.append(entry)
            zx, zy, zm = self._mesh_zero_row(shard, width)
            xs, ys, ms = [], [], []
            for step in plan:
                if step[0] == "zero":
                    entry = (zx, zy, zm)
                elif step[0] == "hit":
                    entry = step[1]
                else:
                    entry = miss_dev[step[1]]
                xs.append(entry[0])
                ys.append(entry[1])
                ms.append(entry[2])
            # committed inputs -> the stack executes on the shard's
            # device; no cross-device bytes
            per_x.append(jnp.stack(xs))
            per_y.append(jnp.stack(ys))
            per_m.append(jnp.stack(ms))
        px = layout.assemble(per_x)
        py = layout.assemble(per_y)
        pm = layout.assemble(per_m)
        self._m_dev_hit.inc(hit_rows)
        self._m_dev_miss.inc(miss_rows)
        st["hit_rows"], st["hit_bytes"] = hit_rows, hit_bytes
        if batch_key is not None:
            nbytes = sum(int(a.nbytes) for a in (px, py, pm))
            with self._mesh_lock:
                self._mesh_memo = (batch_key, (px, py, pm),
                                   hit_bytes + g2_bytes, nbytes)
        return px, py, pm, g2_bytes

    def _mesh_zero_line(self, shard: MeshCacheShard):
        """Shard-local zero line table (the `_zero_line_row` contract,
        committed to the shard's device)."""
        import numpy as np

        with self._mesh_lock:
            row = shard.zero_rows.get("lines")
        if row is None:
            import jax

            row = (jax.device_put(
                       np.zeros(self._bn.LINE_TABLE_SHAPE, np.int32),
                       shard.device),
                   jax.device_put(np.asarray(True), shard.device))
            with self._mesh_lock:
                shard.zero_rows.setdefault("lines", row)
                row = shard.zero_rows["lines"]
        return row

    def _mesh_line_tables(self, st: dict, rows, keys, layout):
        """The mesh precomp path: resolve every batch row's line table
        against ITS device's cache shard, marshal + precompute misses
        on their owning device only (committed inputs keep the
        precompute dispatch device-local), stack per-device slabs and
        assemble the global sharded (B, L, 3, 2, nl) table + (B,)
        infinity flags with zero cross-device traffic. Returns
        (table, inf, transferred g2_bytes)."""
        import jax

        width, bucket = st["width"], st["bucket"]
        rpd = layout.rows_per_device(bucket)
        if keys is not None and all(
                k is not None or not row for row, k in zip(rows, keys)):
            batch_key = (tuple(keys), bucket, "precomp",
                         layout.n_devices)
        else:
            batch_key = None
        st["line_key"] = batch_key
        with self._mesh_lock:
            memo = self._mesh_line_memo
        if batch_key is not None and memo is not None \
                and memo[0] == batch_key:
            tab, inf = memo[1]
            st["hit_rows"] = st["pk_rows"]
            st["hit_bytes"] = memo[2]
            self._m_dev_hit.inc(st["pk_rows"])
            return tab, inf, 0

        per_t, per_i = [], []
        g2_bytes = hit_rows = hit_bytes = miss_rows = 0
        for shard in self._mesh_shards:
            lo = shard.index * rpd
            s_rows = rows[lo:lo + rpd]
            s_keys = (keys[lo:lo + rpd] if keys is not None
                      else [None] * len(s_rows))
            plan = []  # ("zero",) | ("hit", entry) | ("miss", j)
            misses = []  # (row, key)
            with self._mesh_lock:
                for row, key in zip(s_rows, s_keys):
                    if not row:
                        plan.append(("zero",))
                        continue
                    entry = None
                    if key is not None:
                        entry = shard.cache.get((key, "lines"))
                        if entry is not None:
                            shard.cache.move_to_end((key, "lines"))
                    if entry is not None:
                        plan.append(("hit", entry))
                        hit_rows += 1
                        hit_bytes += entry[3]
                        shard.m_hit.inc()
                    else:
                        plan.append(("miss", len(misses)))
                        misses.append((row, key))
                        shard.m_miss.inc()
            miss_dev = []
            if misses:
                mx, my, mm = self._miss_planes(misses, width)
                if st["check"] and self._wire_u16 and mx.size:
                    marshal.assert_canonical_limbs(mx, my)
                g2_bytes += mx.nbytes + my.nbytes + mm.nbytes
                miss_rows += len(misses)
                # a jit over committed inputs compiles per device: the
                # shard's index is part of the shape
                tabs, infs = self._precompute_lines(
                    tuple(jax.device_put(plane, shard.device)
                          for plane in (mx, my, mm)),
                    width, shard.index)
                miss_dev = self._line_entries(tabs, infs, len(misses))
                for (_, key), entry in zip(misses, miss_dev):
                    if key is not None:
                        self._mesh_shard_insert(
                            shard, (key, "lines"), entry)
            slab_t, slab_i = self._stack_line_plan(
                plan, miss_dev, self._mesh_zero_line(shard), shard.index)
            per_t.append(slab_t)
            per_i.append(slab_i)
        tab = layout.assemble(per_t)
        inf = layout.assemble(per_i)
        self._m_dev_hit.inc(hit_rows)
        self._m_dev_miss.inc(miss_rows)
        st["hit_rows"], st["hit_bytes"] = hit_rows, hit_bytes
        if batch_key is not None:
            nbytes = int(tab.nbytes) + int(inf.nbytes)
            with self._mesh_lock:
                self._mesh_line_memo = (batch_key, (tab, inf),
                                        hit_bytes + g2_bytes, nbytes)
        return tab, inf, g2_bytes

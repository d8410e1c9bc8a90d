"""HBM memory ledger: live device-memory accounting + per-program AOT
memory analysis + headroom-coupled capacity signals (ISSUE 18).

The attribution stack can say where every second and every byte of
*bandwidth* of a decode step went (timeline, roofline, cost ledger,
incidents) — but not where a single byte of HBM *resides*. Params, the
slot KV cache, the engine's prefix-KV LRU, and the carried logits all
hold device memory, and until this module the only memory signal in the
tree was a one-shot log warning at engine init. KV-cache memory is the dominant serving-capacity constraint for LLM
inference, and the 70B/disaggregated roadmap items need a *measured*
headroom signal, not a guess.

Three layers, one module:

- **Pool ledger** (:class:`MemoryLedger`): every allocation site that
  creates persistent device state registers the actual pytree under a
  closed pool name (``POOLS``) — the ledger sums leaf ``nbytes`` (and the
  per-device shard split when the tree lives on a >1-device mesh) and
  publishes ``hbm_bytes{pool[, replica][, shard]}`` gauges. Release and
  rebuild re-register under the same handle, so the gauges track the live
  tree, not an estimate of it.
- **Reconciliation**: the ledger total is compared against what the
  backend itself reports (``device.memory_stats()`` — TPU runtimes report
  ``bytes_limit``/``bytes_in_use``; CPU reports nothing). Where the device
  reports, the gauges carry ``reconciliation="measured"`` and a delta
  beyond tolerance raises ``hbm_reconciliation_alerts_total`` (the ledger
  is lying — a leak or a double count). Where it doesn't, the gauges are
  analytic-only and carry ``reconciliation="indicative"`` (an analytic
  limit can be injected — tests and drills do — but the delta is not
  evidence). Exported: ``hbm_bytes_limit`` / ``hbm_headroom_bytes`` /
  ``hbm_reconciliation_delta_bytes``.
- **Per-program AOT analysis**: ``instrument_jit`` (costmodel.py) captures
  ``compiled.memory_analysis()`` once per compiled program — the
  temp/argument/output/peak bytes XLA itself budgeted — as
  ``program_memory_bytes{program, kind}`` gauges, for every program label
  in ``compiles_total`` including ``*_fused`` and ``@tpN``, and counts the
  Pallas custom calls and all-reduces in the compiled module as
  ``program_hlo_ops{program, op}``. This turns the
  70B fit-proof tooling's static math (tools/prove_70b_int8_fit.py) into a
  live instrument. The capture pays a second XLA compile per program, so
  it arms with the exporters (``telemetry.configure``) or explicitly
  (``set_aot_memory_capture``), not in bare library use.

The control plane reads the ledger through
:meth:`MemoryLedger.headroom_frac`: the autoscaler treats a headroom
collapse as a hot signal, and the overload ladder's rung-2 batch cap
engages early when headroom is tight. All of it is SOFT: the ledger never
blocks an admission itself — it explains and forewarns.

Gating follows the house rule: ``set_attribution(False)`` silences the
whole ledger (register/release become no-ops, nothing publishes), and the
bench ``memory_overhead`` A/B flips :func:`set_memory_obs` to prove the
on-cost is noise. Single-threaded like the scheduler loop that drives it.

See docs/OBSERVABILITY.md §Memory signals.
"""

from __future__ import annotations

import logging
import re
from typing import Dict, List, Optional, Tuple

from fairness_llm_tpu.telemetry.registry import get_registry
from fairness_llm_tpu.telemetry.timeline import attribution_on

logger = logging.getLogger(__name__)

# Closed pool set, same stance as incident classes / ring categories: a
# typo'd pool at a call site should fail tests, not open a new label.
POOLS = (
    "params",         # engine parameter tree (per engine instance)
    "kv_contiguous",  # slot KV cache (scheduler._cache): per-position leaves
    "kv_window",      # its window layers' rings: leaves shorter than the row
    "row_state",      # its per-row leaves: recurrent state, whatever the length
    "prefix_cache",   # engine prefix-KV LRU entries
    "logits_carry",   # per-slot carried next-token logits
    "other",          # anything a caller accounts that fits no pool above
)

# Reconciliation tolerance: |device in_use - ledger total| beyond this
# fraction of the device limit raises hbm_reconciliation_alerts_total.
# Generous on purpose — the runtime holds framework buffers (compiled
# executables, donation scratch) no pool ledger should claim to own.
RECONCILE_TOL_FRAC = 0.2

# program_memory_bytes kinds the AOT capture always publishes. ``peak``
# rides along only where the backend reports it (TPU; CPU's
# CompiledMemoryStats has no peak field).
PROGRAM_MEMORY_KINDS = ("argument", "output", "temp")


def tree_device_bytes(tree) -> int:
    """Total bytes of every array leaf in ``tree`` (logical/global bytes —
    a sharded array counts once, not once per device)."""
    import jax

    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


def tree_shard_bytes(tree) -> Dict[int, int]:
    """Per-device bytes of ``tree``'s addressable shards, keyed by device
    id. Empty when everything lives on one device (the common CPU case) —
    the split gauges only publish when there is a split to show. A
    replicated leaf counts its full bytes on EVERY device (that is what it
    costs)."""
    import jax

    out: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards or len(shards) < 2:
            continue
        for sh in shards:
            did = int(sh.device.id)
            out[did] = out.get(did, 0) + int(getattr(sh.data, "nbytes", 0))
    return out


def all_device_memory_stats() -> Dict[int, Dict]:
    """``memory_stats()`` of every local device, by device id: one call
    each; ``{}`` for a device whose backend doesn't implement it (CPU)."""
    import jax

    return {int(d.id): getattr(d, "memory_stats", lambda: None)() or {}
            for d in jax.local_devices()}


def device_memory_stats() -> Dict:
    """``memory_stats()`` of device 0, or ``{}`` where the backend doesn't
    implement it (CPU) — the same defensive shape the engine preflight has
    always used."""
    import jax

    devices = jax.devices()
    if not devices:
        return {}
    return getattr(devices[0], "memory_stats", lambda: None)() or {}


class MemoryLedger:
    """Process-wide per-pool device-memory accounting.

    Entries are keyed ``(pool, name[, replica])`` where ``name`` is the
    caller's stable handle for one allocation site ("engine0", "sched2:
    cache", a prefix hash) — re-registering the same handle REPLACES the
    entry (rebuild semantics), releasing removes it. Gauges always reflect
    the sum over live entries; a (pool, replica) combination that drains
    to zero publishes 0 rather than going stale.
    """

    def __init__(self):
        self.enabled = True
        # (pool, name, replica) -> (bytes, {device_id: bytes})
        self._entries: Dict[Tuple[str, str, str], Tuple[int, Dict[int, int]]] = {}
        # Label combos ever published, so drained ones zero instead of
        # lingering at their last value.
        self._published: set = set()
        self._published_shards: set = set()
        # Injected analytic limit for backends that report no memory_stats
        # (tests, drills, capacity planning on CPU). A REAL device limit
        # always wins.
        self._analytic_limit: Optional[int] = None

    # -- gating ---------------------------------------------------------------

    def _on(self) -> bool:
        return self.enabled and attribution_on()

    # -- registration ---------------------------------------------------------

    def register(self, pool: str, name: str, tree,
                 replica: Optional[str] = None) -> int:
        """Account ``tree``'s device bytes under ``pool`` with handle
        ``name``. Re-registering the same handle replaces the old entry
        (that IS the rebuild path). Returns the bytes accounted (0 when
        the ledger is off)."""
        if pool not in POOLS:
            raise ValueError(f"unknown memory pool {pool!r} "
                             f"(choose from {POOLS})")
        if not self._on():
            return 0
        nbytes = tree_device_bytes(tree)
        shards = tree_shard_bytes(tree)
        self._entries[(pool, name, replica or "")] = (nbytes, shards)
        self._record_ring("register", pool, name, nbytes, replica)
        self.refresh()
        return nbytes

    def release(self, pool: str, name: str,
                replica: Optional[str] = None) -> int:
        """Drop the entry registered under ``(pool, name)``. Missing
        entries are a no-op (double release, or registration happened
        while attribution was off). Returns the bytes released."""
        if not self._on():
            return 0
        entry = self._entries.pop((pool, name, replica or ""), None)
        if entry is None:
            return 0
        self._record_ring("release", pool, name, entry[0], replica)
        self.refresh()
        return entry[0]

    def release_matching(self, name_prefix: str,
                         replica: Optional[str] = None) -> int:
        """Release every entry whose handle starts with ``name_prefix``
        (and matches ``replica`` when given) — the fleet's retirement path
        drops a whole scheduler's pools in one call. Returns total bytes
        released."""
        if not self._on():
            return 0
        victims = [k for k in self._entries
                   if k[1].startswith(name_prefix)
                   and (replica is None or k[2] == replica)]
        freed = 0
        for k in victims:
            nbytes, _ = self._entries.pop(k)
            freed += nbytes
            self._record_ring("release", k[0], k[1], nbytes,
                              k[2] or None)
        if victims:
            self.refresh()
        return freed

    # -- totals ---------------------------------------------------------------

    def pool_bytes(self, pool: str, replica: Optional[str] = None) -> int:
        return sum(v[0] for (p, _, r), v in self._entries.items()
                   if p == pool and (replica is None or r == (replica or "")))

    def total_bytes(self) -> int:
        return sum(v[0] for v in self._entries.values())

    # -- limits / reconciliation ----------------------------------------------

    def set_analytic_limit(self, nbytes: Optional[int]) -> None:
        """Inject a byte budget for backends that report no memory_stats.
        The reconciliation label stays ``indicative`` — an injected limit
        makes headroom math possible, not measured."""
        self._analytic_limit = int(nbytes) if nbytes else None
        if self._on():
            self.refresh()

    def _limits(self, stats: Optional[Dict] = None
                ) -> Tuple[Optional[int], Optional[int], str]:
        """(limit, bytes_in_use, reconciliation_mode). Mode is
        ``measured`` only when the DEVICE reported a limit."""
        if stats is None:
            stats = device_memory_stats()
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use")
        if limit:
            return int(limit), (int(in_use) if in_use else None), "measured"
        return self._analytic_limit, None, "indicative"

    def note_peaks(self) -> None:
        """Each device's high-water mark and limit into
        ``hbm_peak_bytes{device}`` / ``hbm_limit_bytes{device}``. The
        scheduler calls this at the end of every iteration (one
        ``memory_stats()`` call per device), so the peak is in the
        registry while the run still holds its memory; nothing else of
        ``reconcile`` runs there, whose drift alert counts per call."""
        if self._on():
            self._publish_peaks(all_device_memory_stats())

    @staticmethod
    def _publish_peaks(per_device: Dict[int, Dict]) -> None:
        reg = get_registry()
        for dev, st in per_device.items():
            if st.get("peak_bytes_in_use") and st.get("bytes_limit"):
                reg.gauge("hbm_peak_bytes", component="memory",
                          device=str(dev)).set(int(st["peak_bytes_in_use"]))
                reg.gauge("hbm_limit_bytes", component="memory",
                          device=str(dev)).set(int(st["bytes_limit"]))

    def reconcile(self) -> Dict:
        """Compare the ledger against the device's own accounting and
        publish the limit/headroom/delta gauges. Returns the comparison
        (what ``memory-report`` renders and tests assert on), and the
        devices' own high-water marks (``note_peaks``)."""
        per_device = all_device_memory_stats()
        self._publish_peaks(per_device)
        limit, in_use, mode = self._limits(
            per_device[min(per_device)] if per_device else {})
        total = self.total_bytes()
        reg = get_registry()
        lbl = {"component": "memory", "reconciliation": mode}
        reg.gauge("hbm_bytes_total", **lbl).set(total)
        out = {"mode": mode, "ledger_bytes": total, "limit_bytes": limit,
               "bytes_in_use": in_use, "headroom_bytes": None,
               "delta_bytes": None, "alert": False}
        if limit is None:
            return out
        occupied = max(total, in_use or 0)
        headroom = limit - occupied
        reg.gauge("hbm_bytes_limit", **lbl).set(limit)
        reg.gauge("hbm_headroom_bytes", **lbl).set(headroom)
        out["headroom_bytes"] = headroom
        if in_use is not None:
            delta = in_use - total
            reg.gauge("hbm_reconciliation_delta_bytes", **lbl).set(delta)
            out["delta_bytes"] = delta
            if abs(delta) > RECONCILE_TOL_FRAC * limit:
                # The ledger disagrees with the device beyond what
                # framework overhead explains: a pool leak (device high)
                # or a double count (ledger high). Counted, never raised —
                # accounting must not take serving down.
                out["alert"] = True
                reg.counter("hbm_reconciliation_alerts_total",
                            component="memory").inc()
                logger.warning(
                    "hbm ledger reconciliation drift: device in_use %.1f MB"
                    " vs ledger %.1f MB (tolerance %d%% of %.1f GB limit)",
                    in_use / 1e6, total / 1e6,
                    int(RECONCILE_TOL_FRAC * 100), limit / 1e9,
                )
        return out

    # -- headroom -----------------------------------------------------------

    def headroom_bytes(self) -> Optional[int]:
        limit, in_use, _ = self._limits()
        if limit is None:
            return None
        return limit - max(self.total_bytes(), in_use or 0)

    def headroom_frac(self) -> Optional[float]:
        """Headroom as a fraction of the limit — the control-plane soft
        signal (autoscaler hot reason, overload rung-2 cap). None when no
        limit is known (CPU without an injected budget): consumers must
        treat unknown as 'no opinion', never as pressure."""
        limit, in_use, _ = self._limits()
        if limit is None:
            return None
        return max(0.0, (limit - max(self.total_bytes(), in_use or 0))
                   / limit)

    # -- publication ----------------------------------------------------------

    def refresh(self) -> None:
        """Re-publish every pool gauge from the live entries and run
        reconciliation. Called by register/release; callable directly
        after out-of-band changes (tests, reports)."""
        if not self._on():
            return
        reg = get_registry()
        sums: Dict[Tuple[str, str], int] = {}
        shard_sums: Dict[Tuple[str, str, int], int] = {}
        for (pool, _, rep), (nbytes, shards) in self._entries.items():
            sums[(pool, rep)] = sums.get((pool, rep), 0) + nbytes
            for did, b in shards.items():
                key = (pool, rep, did)
                shard_sums[key] = shard_sums.get(key, 0) + b
        for key in self._published - set(sums):
            sums.setdefault(key, 0)
        for key in self._published_shards - set(shard_sums):
            shard_sums.setdefault(key, 0)
        self._published |= set(sums)
        self._published_shards |= set(shard_sums)
        for (pool, rep), nbytes in sums.items():
            lbl = {"component": "memory", "pool": pool}
            if rep:
                lbl["replica"] = rep
            reg.gauge("hbm_bytes", **lbl).set(nbytes)
        for (pool, rep, did), nbytes in shard_sums.items():
            # Shard label matches the @tpN program-label convention: the
            # split a tp=k mesh makes is what these rows show.
            lbl = {"component": "memory", "pool": pool, "shard": f"tp{did}"}
            if rep:
                lbl["replica"] = rep
            reg.gauge("hbm_bytes", **lbl).set(nbytes)
        self.reconcile()

    def _record_ring(self, event: str, pool: str, name: str, nbytes: int,
                     replica: Optional[str]) -> None:
        # Lazy import: flightrecorder imports timeline, memory is below
        # both — but incidents imports flightrecorder too; keep the
        # runtime dependency one-directional at call time.
        from fairness_llm_tpu.telemetry.flightrecorder import (
            get_flight_recorder,
        )

        get_flight_recorder().record(
            "memory", event=event, pool=pool, name=name, bytes=int(nbytes),
            total=int(self.total_bytes()), replica=replica,
        )


# -- process-wide accessors ----------------------------------------------------

_ledger = MemoryLedger()


def get_memory_ledger() -> MemoryLedger:
    return _ledger


def set_memory_ledger(ledger: MemoryLedger) -> MemoryLedger:
    global _ledger
    prev, _ledger = _ledger, ledger
    return prev


class use_memory_ledger:
    """Context manager: route accounting to a fresh (or given) ledger
    inside the block — test isolation, like ``use_registry``."""

    def __init__(self, ledger: Optional[MemoryLedger] = None):
        self.ledger = ledger if ledger is not None else MemoryLedger()
        self._prev: Optional[MemoryLedger] = None

    def __enter__(self) -> MemoryLedger:
        self._prev = set_memory_ledger(self.ledger)
        return self.ledger

    def __exit__(self, *exc) -> None:
        set_memory_ledger(self._prev)


def set_memory_obs(on: bool) -> bool:
    """Flip the whole memory-observability layer (pool ledger + AOT
    program capture) — the bench ``memory_overhead`` A/B's switch.
    Returns the previous ledger-enabled state."""
    global _aot_capture
    ledger = get_memory_ledger()
    prev = ledger.enabled
    ledger.enabled = bool(on)
    _aot_capture = bool(on)
    return prev


# -- per-program AOT memory analysis ------------------------------------------

# The AOT capture costs a SECOND XLA compile per program (jax's AOT
# lower/compile path shares no cache with the jit call path), so it arms
# with the exporters — telemetry.configure() flips it on — or explicitly,
# never by default in bare library/test use.
_aot_capture = False


def set_aot_memory_capture(on: bool) -> bool:
    global _aot_capture
    prev, _aot_capture = _aot_capture, bool(on)
    return prev


def aot_memory_capture_on() -> bool:
    return (_aot_capture and attribution_on()
            and get_memory_ledger().enabled)


def publish_program_memory(program: str, argument: int, output: int,
                           temp: int, peak: Optional[int] = None) -> None:
    """``program_memory_bytes{program, kind}`` gauges — one row per kind,
    values straight from XLA's compiled-module budget (per device on a
    sharded program: memory_analysis reports the per-participant
    module)."""
    reg = get_registry()
    rows = {"argument": argument, "output": output, "temp": temp}
    if peak is not None:
        rows["peak"] = peak
    for kind, val in rows.items():
        reg.gauge("program_memory_bytes", component="memory",
                  program=program, kind=kind).set(max(int(val), 0))


_ALL_REDUCE = re.compile(r"\ball-reduce(-start)?\(")


def count_hlo_ops(hlo_text: str) -> Dict[str, int]:
    """What a compiled module holds of the ops a run has to prove: the
    Pallas kernels, found by the ``name=`` their ``pallas_call`` carries into
    the Mosaic custom call's op_name (ops/quant_matmul.py,
    ops/flash_attention.py, ops/delta_rule.py, ops/pool_attention.py), and
    the all-reduces GSPMD inserts after partitioning (invisible to the jaxpr
    walk)."""
    counts = {"int8_matmul": 0, "grouped_int8_matmul": 0,
              "flash_attention": 0, "kda_step": 0, "gdn_step": 0,
              "pool_attention_own": 0,
              "pool_attention_lead": 0, "all-reduce": 0}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line:
            # The grouped product's name ends in the plain one's: look for
            # it first, and count a call once.
            for kernel in ("grouped_int8_matmul", "int8_matmul",
                           "flash_attention", "kda_step", "gdn_step",
                           "pool_attention_own", "pool_attention_lead"):
                if f"{kernel}/pallas_call" in line:
                    counts[kernel] += 1
                    break
        elif "all-reduce" in line and _ALL_REDUCE.search(line):
            counts["all-reduce"] += 1
    return counts


def count_pool_copies(hlo_text: str, slots: int, cache_len) -> int:
    """Copies of a whole ``[slots, cache_len, ...]`` leaf of the slot pool
    that a compiled module makes as instructions of their own: what a
    program pays per call where the pool's donation did not take, or where
    a read of the leaf made XLA change its layout first. ``cache_len``: the
    row's length, or every length a leaf of the pool has (a window layer's
    ring is shorter than the row). A copy inside a fused computation is that
    fusion's read of the leaf in another layout, not a second leaf, and is
    left out."""
    lengths = "|".join(str(n) for n in (
        (cache_len,) if isinstance(cache_len, int) else cache_len))
    return count_unfused(
        hlo_text, rf"= \w+\[{slots},(?:{lengths})[,\]][^=]* copy\(")


def count_rolled_rows(hlo_text: str, rows: int, P: int, pool,
                      lengths) -> int:
    """Instructions of their own that make a ``[rows, n + min(P, n), ...]``
    value of a leaf of ``pool`` whose row is ``[n, ...]`` (``n`` one of
    ``lengths``: a per-position leaf): a batch's rows of the leaf doubled over
    a bucket's extent of ``P``, the temporary that rolls donor rows by one
    gather (``stepbuilder.build_serve_prefill`` in a short bucket)."""
    import jax

    doubled = "|".join(sorted({re.escape(",".join(map(str, (
        rows, x.shape[1] + min(P, x.shape[1])) + tuple(x.shape[2:]))))
        for x in jax.tree_util.tree_leaves(pool)
        if len(x.shape) > 1 and x.shape[1] in lengths}))
    return count_unfused(
        hlo_text, rf"= \w+\[(?:{doubled})\](?:\{{[^}}]*\}})? "
        r"(?!get-tuple-element\(|bitcast\(|parameter\()[\w-]+\(")


def count_unfused(hlo_text: str, pattern: str) -> int:
    """Lines of a compiled module outside its fused computations that match
    ``pattern``: instructions that XLA materializes."""
    found = re.compile(pattern)
    count, fused = 0, False
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(", 1)[0]
        elif not fused and found.search(line):
            count += 1
    return count


def publish_program_hlo_ops(program: str, hlo_text: str) -> None:
    """``program_hlo_ops{program, op}`` gauges from :func:`count_hlo_ops` —
    the witness that a kernel was not replaced by an XLA expression and that
    a sharded program communicates. A program label compiled at several
    shapes keeps the FEWEST of each op seen, so one shape that lost a kernel
    cannot hide behind another."""
    reg = get_registry()
    for op, n in count_hlo_ops(hlo_text).items():
        labels = dict(component="memory", program=program, op=op)
        prev = reg.peek("program_hlo_ops", **labels)
        reg.gauge("program_hlo_ops", **labels).set(
            n if prev is None else min(prev.value, n))


def capture_program_memory(jit_fn, pyfn, program: str, args) -> bool:
    """AOT-compile ``jit_fn`` at ``args``' shapes and publish what XLA
    budgeted for it. Called by ``InstrumentedJit`` once per program on its
    first capture-armed call (inside the caller's mesh context, so a tp
    program lowers SPMD exactly like the live one). Raises on failure —
    the caller owns the once-only containment flag."""
    if not aot_memory_capture_on():
        return False
    import jax

    lowered = jit_fn.lower(*args)
    compiled = lowered.compile()
    publish_program_hlo_ops(program, compiled.as_text())
    ma = compiled.memory_analysis()
    if ma is not None and hasattr(ma, "temp_size_in_bytes"):
        publish_program_memory(
            program,
            argument=int(ma.argument_size_in_bytes),
            output=int(ma.output_size_in_bytes),
            temp=int(ma.temp_size_in_bytes),
            peak=int(getattr(ma, "peak_memory_in_bytes", 0)) or None,
        )
        get_registry().gauge(
            "program_memory_bytes", component="memory", program=program,
            kind="generated_code",
        ).set(int(getattr(ma, "generated_code_size_in_bytes", 0)))
        return True
    # Backend compiled but reports no memory analysis: fall back to the
    # aval math (arguments from the real args, outputs from an
    # eval_shape) so the program still publishes its transfer footprint.
    out_tree = jax.eval_shape(pyfn, *args)
    publish_program_memory(
        program,
        argument=tree_device_bytes(args),
        output=sum(int(v.size) * int(v.dtype.itemsize)
                   for v in jax.tree_util.tree_leaves(out_tree)
                   if hasattr(v, "size")),
        temp=0,
    )
    return True


# -- snapshot reading / report -------------------------------------------------


def has_memory_data(snap: Dict) -> bool:
    return any(g.get("name") == "hbm_bytes" for g in snap.get("gauges", []))


def _fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{int(n)} B"


def render_memory_report(snap: Dict) -> str:
    """The ``memory-report`` CLI section: per-pool residency, the
    reconciliation verdict, and the per-program AOT memory table."""
    gauges = snap.get("gauges", [])
    lines: List[str] = ["HBM memory ledger", "=" * 17]

    def val(name) -> Optional[Dict]:
        for g in gauges:
            if g.get("name") == name:
                return g
        return None

    total = val("hbm_bytes_total")
    mode = (total or {}).get("labels", {}).get("reconciliation")
    limit = val("hbm_bytes_limit")
    headroom = val("hbm_headroom_bytes")
    delta = val("hbm_reconciliation_delta_bytes")
    alerts = sum(c.get("value", 0) for c in snap.get("counters", [])
                 if c.get("name") == "hbm_reconciliation_alerts_total")
    if total is None:
        lines.append("no hbm_bytes gauges in this snapshot (the ledger "
                     "never registered a pool — attribution off, or a "
                     "pre-ISSUE-18 run)")
        return "\n".join(lines)
    if mode == "measured":
        lines.append("reconciliation: measured (device reports "
                     "memory_stats; delta gauge is evidence)")
    else:
        lines.append("reconciliation: indicative (backend reports no "
                     "memory_stats — analytic accounting only)")
    lines.append(
        f"ledger total {_fmt_bytes(total.get('value'))}"
        + (f"  limit {_fmt_bytes(limit.get('value'))}" if limit else "")
        + (f"  headroom {_fmt_bytes(headroom.get('value'))}"
           if headroom else "")
        + (f"  delta vs device {_fmt_bytes(delta.get('value'))}"
           if delta else "")
    )
    if alerts:
        lines.append(f"RECONCILIATION ALERTS: {int(alerts)} (ledger vs "
                     "device drift beyond tolerance)")
    # Pool table: unsharded rows first, then the per-shard split.
    pool_rows = [g for g in gauges if g.get("name") == "hbm_bytes"]
    plain = [g for g in pool_rows if "shard" not in g.get("labels", {})]
    sharded = [g for g in pool_rows if "shard" in g.get("labels", {})]
    if plain:
        lines.append("")
        lines.append(f"{'pool':<14} {'replica':<12} {'bytes':>12}")
        for g in sorted(plain, key=lambda g: (
                g["labels"].get("pool", ""), g["labels"].get("replica", ""))):
            lb = g.get("labels", {})
            lines.append(f"{lb.get('pool', '?'):<14} "
                         f"{lb.get('replica', '-'):<12} "
                         f"{_fmt_bytes(g.get('value')):>12}")
    if any(g.get("value", 0) for g in sharded):
        lines.append("")
        lines.append(f"{'pool':<14} {'shard':<8} {'bytes':>12}")
        for g in sorted(sharded, key=lambda g: (
                g["labels"].get("pool", ""), g["labels"].get("shard", ""))):
            lb = g.get("labels", {})
            lines.append(f"{lb.get('pool', '?'):<14} "
                         f"{lb.get('shard', '?'):<8} "
                         f"{_fmt_bytes(g.get('value')):>12}")
    # Per-program AOT table.
    prog: Dict[str, Dict[str, float]] = {}
    for g in gauges:
        if g.get("name") != "program_memory_bytes":
            continue
        lb = g.get("labels", {})
        prog.setdefault(lb.get("program", "?"), {})[lb.get("kind", "?")] = \
            float(g.get("value", 0.0))
    if prog:
        lines.append("")
        lines.append("per-program AOT memory (compiled.memory_analysis, "
                     "per device)")
        lines.append(f"{'program':<26} {'argument':>10} {'output':>10} "
                     f"{'temp':>10} {'peak':>10}")
        for p in sorted(prog):
            k = prog[p]
            lines.append(
                f"{p:<26} {_fmt_bytes(k.get('argument')):>10} "
                f"{_fmt_bytes(k.get('output')):>10} "
                f"{_fmt_bytes(k.get('temp')):>10} "
                f"{_fmt_bytes(k.get('peak')):>10}"
            )
    return "\n".join(lines)

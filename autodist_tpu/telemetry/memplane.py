"""HBM memory plane: owner-attributed census, budget, pressure, OOM forensics.

The raw gauges existed before this module — ``device.mem.bytes_in_use.d<id>``
from the allocator, ``device.live_bytes`` from ``jax.live_arrays()`` — but
nothing said WHOSE bytes those were, whether a candidate plan would fit
before a compile probe was spent on it, or what was resident when an OOM
killed the run. This module closes those three gaps with one registry:

- **Tag registry** — :func:`tag` claims device bytes for a named owner
  (``params`` / ``opt_state`` / ``kv_pages`` / ``prefetch`` / ``snapshots``).
  A tree claim holds WEAK references to its ``jax.Array`` leaves, so a
  donated/freed tree's claim evaporates with it (no owner ever pins memory
  just by being observed); an integer claim is static until re-tagged.
  :func:`attribute` turns the claims plus the live-bytes gauge into
  ``mem.owned.*`` values, with ``other`` = live minus claimed, clamped at
  zero — the leak-hunting residual.
- **Budget** — :func:`device_budget` resolves the per-device usable budget
  from the first source that answers: the measured allocator limit
  (``bytes_limit`` x 0.8), the ``AUTODIST_MEM_BUDGET`` override, else the
  8 GiB default (with a one-time warning — a silently defaulted budget is
  how the async-PS memory rule ran blind on CPU). The winning source is
  booked as ``mem.budget_source`` (0 default / 1 env / 2 measured).
- **Pressure** — :func:`current_pressure` is the worst device's
  ``bytes_in_use / bytes_limit`` (the ratio the shipped ``mem_pressure``
  alert rule thresholds); on backends with no allocator stats it degrades
  to ``live_bytes / budget`` so an injected squeeze (a tiny
  ``AUTODIST_MEM_BUDGET``) still drives the same plane. Serving admission
  reads it through :func:`kv_admission_holdback`: past the threshold the
  paged-KV allocator holds back a fraction of its reservable pages, so the
  fleet sheds load before the allocator dies.
- **OOM forensics** — :func:`is_oom_error` recognizes RESOURCE_EXHAUSTED
  at the runner's dispatch sites; :func:`record_oom` books the ``mem.oom``
  counter + event and triggers the flight recorder (debounced), whose
  manifest carries :func:`memory_section`: the census, the per-program
  memory ledger, the last-K ``device.mem`` history samples, and the HBM
  account's predicted peak against the allocator's own.
- **HBM account** — what a chip holds at a fenced log boundary, whose it
  is, what a running step adds and what is left, in a telemetry-enabled
  ``train()``: the section of that name below.

Everything degrades to a no-op shell: :func:`memory_snapshot` returns the
same keys armed or not (the ``status`` wire contract), and every sampling
failure is swallowed at debug — diagnostics must never break the run.
"""

import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from autodist_tpu import const
from autodist_tpu.telemetry import export as _export
from autodist_tpu.telemetry import metrics as _metrics
from autodist_tpu.telemetry import spans as _spans
from autodist_tpu.utils import logging

__all__ = ["OWNERS", "tag", "untag", "census", "attribute", "device_budget",
           "pressure_threshold", "current_pressure", "kv_admission_holdback",
           "is_oom_error", "record_oom", "memory_snapshot", "memory_section",
           "device_stats", "hbm_account", "open_hbm_account",
           "book_step_hbm", "book_hbm_boundary", "reset"]

# The attribution vocabulary: every claim lands in one of these buckets, and
# the census books exactly these plus the ``other`` residual (a stable gauge
# family — scrapers see the same series whether an owner is present or not).
OWNERS = ("params", "opt_state", "kv_pages", "prefetch", "snapshots")

DEFAULT_BUDGET_BYTES = 8 << 30     # the historical auto-strategy fallback
BUDGET_FRACTION = 0.8              # usable share of the measured limit
KV_HOLDBACK_FRACTION = 0.25        # reservable pages withheld under pressure
_PRESSURE_CACHE_S = 1.0            # admission-path refresh throttle
_SOURCE_CODE = {"default": 0.0, "env": 1.0, "measured": 2.0}

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Out of memory",
                "out of memory", "Allocation failure", "allocating")


class _Claim:
    """One owner's claim: either a static byte count or weakrefs to the
    ``jax.Array`` leaves of a tagged tree (dead/donated leaves drop out),
    each with the bytes of a shard and the local devices that hold one."""

    __slots__ = ("nbytes", "refs")

    def __init__(self, nbytes: Optional[int] = None,
                 refs: Optional[List[Tuple[Any, int, Tuple[int, ...]]]] = None):
        self.nbytes = nbytes
        self.refs = refs

    def live(self) -> Tuple[int, bool]:
        """(live bytes, any leaf still alive). A tree claim's bytes are the
        most one device holds of its live leaves: a chip's bytes, the unit
        of the allocator's readings. Static claims are always alive; a tree
        claim whose every leaf died reports dead so the registry can prune
        it."""
        if self.refs is None:
            return int(self.nbytes or 0), True
        per_dev: Dict[int, int] = {}
        alive = False
        for ref, nb, devs in self.refs:
            leaf = ref()
            if leaf is None:
                continue
            try:
                if leaf.is_deleted():     # donated buffers keep the pyobject
                    continue
            except (AttributeError, RuntimeError, TypeError):
                pass
            alive = True
            for dev in devs:
                per_dev[dev] = per_dev.get(dev, 0) + nb
        return max(per_dev.values(), default=0), alive


_LOCK = threading.Lock()
_CLAIMS: Dict[str, Dict[str, _Claim]] = {}
_WARNED_DEFAULT = [False]
_PRESSURE = {"value": 0.0, "t": 0.0, "set": False}


def tag(owner: str, tree_or_nbytes: Any,
        key: str = "default") -> Tuple[Dict[int, int], int]:
    """Claim ``owner``'s device bytes for the census. An int/float claims a
    static byte count; anything else is treated as a pytree whose
    ``jax.Array`` leaves are weakly referenced and counted by the shards a
    device holds (``export.leaf_device_bytes``: a tree sharded four ways
    claims a quarter; the claim follows the arrays' lifetime — re-tagging at
    each boundary replaces the claim, a freed tree's claim evaporates on its
    own). ``key`` scopes concurrent claimants of one owner (two paged
    engines in one process). Returns what the walk counted, as
    ``export.device_bytes`` would (``{device id: bytes}`` of the claimed
    leaves, bytes of the host leaves it passed over), so a caller that needs
    the tree's bytes beside the claim walks it once."""
    per_dev: Dict[int, int] = {}
    host = 0
    if isinstance(tree_or_nbytes, (int, float)) \
            and not isinstance(tree_or_nbytes, bool):
        claim = _Claim(nbytes=int(tree_or_nbytes))
    else:
        try:
            import jax
            refs: List[Tuple[Any, int, Tuple[int, ...]]] = []
            for leaf in jax.tree_util.tree_leaves(tree_or_nbytes):
                held = _export.leaf_device_bytes(leaf)
                if held is None:       # the census counts device bytes
                    host += int(getattr(leaf, "nbytes", 0) or 0)
                    continue
                if not held[1]:
                    continue
                try:
                    refs.append((weakref.ref(leaf), *held))
                except TypeError:      # exotic leaf: skip, never pin
                    continue
                for dev in held[1]:
                    per_dev[dev] = per_dev.get(dev, 0) + held[0]
            claim = _Claim(refs=refs)
        except Exception as e:  # noqa: BLE001 — a census tag must never fail
            logging.debug("memplane.tag(%s) skipped: %s", owner, e)
            return per_dev, host
    with _LOCK:
        entries = _CLAIMS.setdefault(str(owner), {})
        entries[str(key)] = claim
        # Opportunistic prune so churny taggers (prefetch) stay bounded (the
        # new claim's leaves were alive a moment ago: not walked again).
        for k in [k for k, c in entries.items()
                  if (not c.refs if c is claim and c.refs is not None
                      else not c.live()[1])]:
            del entries[k]
    return per_dev, host


def untag(owner: str, key: str = "default") -> None:
    """Drop one claim (idempotent)."""
    with _LOCK:
        entries = _CLAIMS.get(str(owner))
        if entries:
            entries.pop(str(key), None)


def reset() -> None:
    """Drop every claim, the pressure cache and the HBM account's opening
    reading (tests)."""
    _PEAK_AT_OPEN[0] = None
    with _LOCK:
        _CLAIMS.clear()
    _PRESSURE.update(value=0.0, t=0.0, set=False)
    _WARNED_DEFAULT[0] = False


def census() -> Dict[str, int]:
    """Live claimed bytes per owner (dead tree claims pruned as a side
    effect). Owners with no claim are absent — :func:`attribute` restores
    the full stable vocabulary."""
    out: Dict[str, int] = {}
    with _LOCK:
        for owner, entries in list(_CLAIMS.items()):
            total = 0
            for key in list(entries):
                nbytes, alive = entries[key].live()
                if not alive:
                    del entries[key]
                    continue
                total += nbytes
            if entries:
                out[owner] = total
            else:
                del _CLAIMS[owner]
    return out


def attribute(live_bytes: int) -> Dict[str, int]:
    """The owner-attributed view of ``live_bytes``: every :data:`OWNERS`
    bucket (0 when unclaimed) plus ``other`` — live minus claimed, CLAMPED
    at zero (claims can overshoot the live gauge when an owner tags bytes
    the live census does not see; the residual is a leak detector, and a
    negative leak is a lie)."""
    counts = census()
    out = {owner: int(counts.get(owner, 0)) for owner in OWNERS}
    claimed = sum(out.values())
    out["other"] = max(0, int(live_bytes) - claimed)
    return out


def device_stats(device) -> Optional[Dict[str, Any]]:
    """The allocator's statistics of one device (``memory_stats()``), or
    None where the backend keeps none (CPU) or the call fails: every reading
    of the plane and of the HBM account goes through here."""
    try:
        return device.memory_stats() or None
    except (RuntimeError, ValueError, TypeError, AttributeError):
        return None


# ------------------------------------------------------------------ budget

def device_budget() -> Tuple[int, str]:
    """Per-device usable memory budget and its source: ``measured``
    (``bytes_limit`` x 0.8 from the allocator), ``env``
    (``AUTODIST_MEM_BUDGET`` bytes), or ``default`` (8 GiB, warned once —
    a budget nobody chose should not be a budget nobody sees). Books
    ``mem.budget_bytes`` / ``mem.budget_source``."""
    budget, source = 0, ""
    try:
        import jax
        limit = min((int((device_stats(d) or {}).get("bytes_limit", 0))
                     for d in jax.local_devices()), default=0)
        if limit > 0:
            budget, source = int(limit * BUDGET_FRACTION), "measured"
    except Exception as e:  # noqa: BLE001 — CPU/sim backends report nothing
        logging.debug("memory budget probe unavailable: %s", e)
    if not budget:
        try:
            env = int(const.ENV.AUTODIST_MEM_BUDGET.val)
        except (TypeError, ValueError):
            env = 0
        if env > 0:
            budget, source = env, "env"
    if not budget:
        budget, source = DEFAULT_BUDGET_BYTES, "default"
        if not _WARNED_DEFAULT[0]:
            _WARNED_DEFAULT[0] = True
            logging.warning(
                "memory plane: no allocator limit and no AUTODIST_MEM_BUDGET "
                "— memory rules (async-PS optimizer choice, autotune "
                "pre-flight) run on the %d GiB default",
                DEFAULT_BUDGET_BYTES >> 30)
    try:
        _metrics.gauge("mem.budget_bytes").set(budget)
        _metrics.gauge("mem.budget_source").set(_SOURCE_CODE[source])
    except Exception:  # noqa: BLE001 — booking is best-effort
        pass
    return budget, source


def pressure_threshold() -> float:
    """The ``AUTODIST_MEM_PRESSURE`` ratio past which the plane reacts
    (the shipped alert rule's value and the KV holdback trigger)."""
    try:
        value = float(const.ENV.AUTODIST_MEM_PRESSURE.val)
    except (TypeError, ValueError):
        return 0.92
    return value if value > 0 else 0.92


def _measure_pressure() -> float:
    """Worst device ``bytes_in_use / bytes_limit``; live-bytes over budget
    when no device reports allocator stats."""
    import jax
    worst = None
    try:
        for d in jax.local_devices():
            stats = device_stats(d)
            if not stats:
                continue
            limit = int(stats.get("bytes_limit", 0) or 0)
            if limit <= 0:
                continue
            ratio = int(stats.get("bytes_in_use", 0) or 0) / limit
            worst = ratio if worst is None else max(worst, ratio)
    except RuntimeError:
        pass
    if worst is None:
        live = _export.opt_state_bytes(jax.live_arrays())
        budget, _ = device_budget()
        worst = live / budget if budget > 0 else 0.0
    return float(worst)


def current_pressure(max_age_s: float = _PRESSURE_CACHE_S) -> float:
    """The pressure ratio, cached for ``max_age_s`` (the serving admission
    path reads it per request — one allocator probe per second, not per
    admission). Books ``mem.pressure`` on refresh; failures return the
    last value (diagnostics never gate admission on a backend hiccup)."""
    now = time.monotonic()
    if _PRESSURE["set"] and now - _PRESSURE["t"] < max_age_s:
        return _PRESSURE["value"]
    try:
        value = _measure_pressure()
        _metrics.gauge("mem.pressure").set(round(value, 6))
    except Exception as e:  # noqa: BLE001
        logging.debug("memory pressure sampling unavailable: %s", e)
        return _PRESSURE["value"]
    _PRESSURE.update(value=value, t=now, set=True)
    return value


def kv_admission_holdback(usable_pages: int) -> int:
    """Pages the paged-KV allocator should withhold from NEW reservations:
    0 below the pressure threshold, ``KV_HOLDBACK_FRACTION`` of the usable
    pool at/above it (in-flight requests keep their reservations — the
    engine sheds admissions, the allocator never dies mid-decode)."""
    if usable_pages <= 0:
        return 0
    if current_pressure() < pressure_threshold():
        return 0
    return max(1, int(usable_pages * KV_HOLDBACK_FRACTION))


# ------------------------------------------------------------------ OOM

def is_oom_error(exc: BaseException) -> bool:
    """Does this look like a device allocator exhaustion? XLA surfaces OOM
    as ``XlaRuntimeError: RESOURCE_EXHAUSTED: ...`` (type match is on the
    NAME — the class moved across jaxlib versions)."""
    msg = str(exc)
    if any(marker in msg for marker in _OOM_MARKERS):
        return type(exc).__name__ == "XlaRuntimeError" \
            or "RESOURCE" in msg.upper() or "memory" in msg.lower()
    return False


def record_oom(where: str, exc: BaseException) -> None:
    """Book the OOM (``mem.oom`` counter + structured event), refresh the
    pressure gauge, and trigger the flight recorder THROUGH its debounce —
    the manifest's ``memory`` section is the autopsy. Never raises: the
    caller re-raises the real error and forensics must not mask it."""
    try:
        _metrics.counter("mem.oom").inc()
        _metrics.event("mem.oom", where=str(where), error=str(exc)[:300])
        current_pressure(max_age_s=0.0)
        from autodist_tpu.telemetry import recorder as _recorder
        _recorder.maybe_record(f"oom.{where}")
    except Exception as e:  # noqa: BLE001 — forensics never mask the OOM
        logging.debug("OOM forensics capture failed: %s", e)


# ------------------------------------------------------------ HBM account
#
# One account of a chip's HBM over a run of ``train()``, every term in bytes
# ONE chip holds, read on the fullest chip:
#
# - at a fenced log boundary (the device idle between two steps):
#   ``train.hbm.resident_bytes`` / ``.limit_bytes`` (the allocator's
#   ``bytes_in_use`` / ``bytes_limit``), ``.state_bytes`` (the TrainState's
#   leaves by that chip's shards), ``.unowned_bytes`` = resident - state
#   (a caller's copy of the parameters, device batches, the snapshot ring,
#   leaks), ``.allocator_peak_bytes`` / ``.allocator_peak_rise_bytes``
#   (``peak_bytes_in_use`` and its rise since ``train()``'s first pull: 0
#   says the process's peak was set before it);
# - once a step signature (``runner._dispatch_span``): the compiled step's
#   own ``memory_analysis()`` as ``step.hbm.argument_bytes`` / ``.temp_bytes``
#   / ``.output_bytes`` / ``.alias_bytes`` / ``.code_bytes``;
# - the identity :func:`hbm_account` states: ``train.hbm.predicted_bytes``
#   and ``.headroom_bytes``.
#
# This allocator books a program's temporaries nowhere (PERF.md section 7,
# PR 48: a 5 ms sampler read the boundary's level all through the window in
# eight cells), so no reading is taken while a step runs: the compiler's
# count is the step's share, and ``allocator_peak_rise_bytes`` turning
# positive in a window of steps alone is the sign that this has changed.


def hbm_account(resident: int, state: int, limit: int, argument: int,
                temp: int, output: int, alias: int) -> Dict[str, int]:
    """The identity, stated once. While a step runs a chip holds what it
    held at the boundary, the step's arguments that were not there (the
    batch: ``argument - state``), its temporaries, and its outputs less
    those that alias the donated state::

        predicted = resident + (argument - state) + temp + output - alias

    and ``headroom_bytes`` is what the limit leaves above that."""
    predicted = resident + (argument - state) + temp + output - alias
    return {"predicted_bytes": int(predicted),
            "headroom_bytes": int(limit - predicted)}


_PEAK_AT_OPEN: List[Optional[int]] = [None]


def _allocator_peak(stats) -> Optional[int]:
    """The highest ``peak_bytes_in_use`` of ``stats`` (an iterable of
    ``memory_stats()`` readings), None where none carries one."""
    return max((int(s["peak_bytes_in_use"]) for s in stats
                if s and s.get("peak_bytes_in_use") is not None),
               default=None)


def open_hbm_account() -> None:
    """Note the allocator's lifetime peak as ``train()`` finds it at its
    first pull (a telemetry-enabled run only): what
    ``train.hbm.allocator_peak_rise_bytes`` rises from."""
    try:
        import jax
        _PEAK_AT_OPEN[0] = _allocator_peak(
            device_stats(d) for d in jax.local_devices())
    except RuntimeError:
        _PEAK_AT_OPEN[0] = None


def book_step_hbm(memory: Dict[str, Any], seconds: float,
                  kept_bytes: int = 0) -> None:
    """The compiled step's own account into ``step.hbm.*`` (the runner, once
    a signature: the program most lately compiled is the one the identity
    reads), what its checkpointed layers keep on a chip into
    ``step.hbm.kept_bytes`` where they keep anything, and what taking the
    account cost into ``step.hbm.account_s``."""
    from autodist_tpu.telemetry import profiling as _profiling
    _metrics.counter("step.hbm.account_s").inc(seconds)
    for field in _profiling.MEMORY_FIELDS:
        if memory.get(field) is not None:   # generated_code_bytes: code_bytes
            _metrics.gauge("step.hbm." + field.replace("generated_", "")
                           ).set(int(memory[field]))
    if kept_bytes:
        _metrics.gauge("step.hbm.kept_bytes").set(int(kept_bytes))


def _gauge_value(name: str) -> Optional[int]:
    instrument = _metrics.registry().get(name)
    return None if instrument is None else int(instrument.value)


def book_hbm_boundary(stats: Dict[int, Dict[str, Any]],
                      state_bytes: Dict[int, int]) -> int:
    """The account's boundary readings from ``stats`` (``{device id:
    memory_stats()}``) and ``state_bytes`` (``{device id: bytes}`` of the
    ``TrainState``), both as ``sample_device_memory`` just took them; states
    the identity where the step's account is booked. Returns the gauges
    written."""
    if not stats:
        return 0
    chip = max(stats, key=lambda d: int(stats[d].get("bytes_in_use", 0) or 0))
    resident = int(stats[chip].get("bytes_in_use", 0) or 0)
    limit = int(stats[chip].get("bytes_limit", 0) or 0)
    held = state_bytes.get(chip, max(state_bytes.values(), default=0))
    book = {"resident_bytes": resident, "limit_bytes": limit,
            "state_bytes": held, "unowned_bytes": resident - held}
    peak = _allocator_peak(stats.values())
    if peak is not None:
        book["allocator_peak_bytes"] = peak
        if _PEAK_AT_OPEN[0] is not None:
            book["allocator_peak_rise_bytes"] = peak - _PEAK_AT_OPEN[0]
    step = {term: _gauge_value(f"step.hbm.{term}_bytes")
            for term in ("argument", "temp", "output", "alias")}
    if None not in step.values():
        book.update(hbm_account(resident, held, limit, **step))
    for name, value in book.items():
        _metrics.gauge(f"train.hbm.{name}").set(value)
    return len(book)


# ------------------------------------------------------------- snapshots

def _armed() -> bool:
    """The plane is armed when telemetry records or anyone tagged bytes."""
    with _LOCK:
        has_claims = bool(_CLAIMS)
    return has_claims or _spans.enabled()


def memory_snapshot() -> Dict[str, Any]:
    """The ``status`` wire section: a STABLE shell (same keys armed or
    not), filled with the census / pressure / budget / per-device stats
    when the plane is armed. ``live_bytes`` is what the fullest chip holds
    of ``jax.live_arrays()``, the unit of ``owned`` and of the gauge
    ``device.live_bytes``. Cheap enough for a 2 s console poll."""
    shell: Dict[str, Any] = {"owned": {}, "live_bytes": 0, "pressure": 0.0,
                             "budget_bytes": 0, "budget_source": "",
                             "devices": {}}
    if not _armed():
        return shell
    try:
        import jax
        live = _export.opt_state_bytes(jax.live_arrays())
        shell["live_bytes"] = live
        shell["owned"] = attribute(live)
        budget, source = device_budget()
        shell["budget_bytes"], shell["budget_source"] = budget, source
        shell["pressure"] = round(current_pressure(), 6)
        for d in jax.local_devices():
            stats = device_stats(d)
            if not stats:
                continue
            row = {"bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
                   "bytes_limit": int(stats.get("bytes_limit", 0) or 0)}
            if stats.get("peak_bytes_in_use") is not None:
                row["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
            shell["devices"][f"d{d.id}"] = row
    except Exception as e:  # noqa: BLE001 — a status poll must not 500
        logging.debug("memory snapshot unavailable: %s", e)
    return shell


def memory_section(history_k: int = 8) -> Dict[str, Any]:
    """The flight-recorder manifest section: :func:`memory_snapshot` plus
    the per-program memory ledger, the last-``history_k`` ``device.mem`` /
    ``mem.*`` / ``train.hbm.*`` / ``step.hbm.*`` history samples, and the
    numbers an OOM autopsy opens with: ``predicted_peak_bytes`` (the HBM
    account's ``train.hbm.predicted_bytes``: what a chip holds while a step
    runs, by the last log boundary of a telemetry-enabled ``train()``; None
    in a process that closed none), ``live_peak_bytes`` (the allocator's own
    ``peak_bytes_in_use`` now, the fullest chip's; its ``bytes_in_use``
    where the backend keeps no peak, ``live_bytes`` where it keeps no
    statistics) and ``peak_delta_bytes`` (live less predicted, None without
    a prediction)."""
    section = memory_snapshot()
    try:
        from autodist_tpu.telemetry import profiling as _profiling
        programs: Dict[str, Dict[str, Any]] = {}
        for sig, rec in _profiling.program_costs().items():
            programs[sig] = {"kind": rec.kind, **{
                field: getattr(rec, field)
                for field in _profiling.MEMORY_FIELDS}}
        section["programs"] = programs
    except Exception:  # noqa: BLE001 — ledger is optional in the autopsy
        section["programs"] = {}
    try:
        from autodist_tpu.telemetry import history as _history
        hist = _history.get_history()
        tail: List[Dict[str, Any]] = []
        if hist is not None:
            for sample in hist.samples()[-max(1, history_k):]:
                row = {k: v for k, v in sample.items()
                       if k == "t_wall_s" or k == "step"
                       or k.startswith("device.mem.")
                       or k.startswith("device.live_")
                       or k.startswith(("mem.", "train.hbm.", "step.hbm."))}
                tail.append(row)
        section["history"] = tail
    except Exception:  # noqa: BLE001
        section["history"] = []
    predicted = _gauge_value("train.hbm.predicted_bytes")
    live = max((row.get("peak_bytes_in_use", row["bytes_in_use"])
                for row in section["devices"].values()),
               default=section["live_bytes"])
    section["predicted_peak_bytes"] = predicted
    section["live_peak_bytes"] = live
    section["peak_delta_bytes"] = None if predicted is None \
        else live - predicted
    return section

"""Continuous policy engine over the subtree-rollup tree (DESIGN.md
§14.4) — the Robinhood half of the admin story (PAPERS.md): declarative
retention/quota rules evaluated continuously against the changelog feed
instead of periodic full-namespace scans.

Three rule kinds, all declarative:

- ``max_bytes``: a subtree (project dir) must stay under a byte budget;
- ``retention``: a subtree must hold no files older than ``max_age_s``
  (age = REF_TIME - atime, judged at the rollup histogram's bucket
  grain — conservative: only files in buckets ENTIRELY older than the
  limit count, so a violation is never a false positive);
- ``uid_quota``: one user's total bytes must stay under a budget
  (evaluated against the aggregate index when attached, else a scan).

Incrementality is the point. Each ``evaluate(watermark)`` sweep gates
subtree rules on ``HierarchyIndex.change_mark`` — an unchanged mark
proves the subtree's rollup did not move, so the rule's verdict stands
without touching the tree — and gates uid rules on the ingest watermark
(a chown changes per-user totals without moving any subtree rollup, so
marks alone must not gate them). ``stats`` counts evaluated vs skipped
rules per sweep; tests assert incrementality against those counters and
against the tree's ``propagated`` work counter, not wall clock.

Violations form a stream with edges: a rule entering violation emits an
``enter`` event, leaving emits ``exit``, staying violated emits nothing
(level-triggered state, edge-triggered delivery — the dashboard panel
shows ``active`` levels, the event deque feeds alerting). Delivery is
at-most-once per edge into a bounded deque: an unread event can be
evicted by newer ones (``maxlen``), but ``active`` always reflects the
current truth, so a consumer that misses edges resynchronizes by
diffing levels.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from repro.core import hierarchy as hier
from repro.core.telemetry import resolve as _resolve_tel

RULE_KINDS = ("max_bytes", "retention", "uid_quota")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One declarative policy rule. ``path`` roots the subtree kinds
    ('' = whole namespace); ``limit_bytes`` bounds max_bytes/uid_quota;
    ``max_age_s`` bounds retention; ``uid`` selects the quota'd user."""
    name: str
    kind: str
    path: str = ""
    limit_bytes: Optional[int] = None
    max_age_s: Optional[float] = None
    uid: Optional[int] = None

    def __post_init__(self):
        if self.kind not in RULE_KINDS:
            raise ValueError(
                f"unknown rule kind {self.kind!r}; expected one of "
                f"{sorted(RULE_KINDS)}")
        need = {"max_bytes": ("limit_bytes",),
                "retention": ("max_age_s",),
                "uid_quota": ("limit_bytes", "uid")}[self.kind]
        for f in need:
            if getattr(self, f) is None:
                raise ValueError(
                    f"rule {self.name!r} ({self.kind}) requires {f!r}")


def retention_min_bucket(max_age_s: float) -> int:
    """First atime-histogram bucket whose ENTIRE age range exceeds
    ``max_age_s``: bucket b spans ages [edge[b-1], edge[b]), so the
    cutoff is one past the leftmost edge >= the limit. Files in earlier
    buckets may or may not be over age — the bucket grain cannot tell —
    and are deliberately not counted (no false-positive violations)."""
    return int(np.searchsorted(hier._EDGES, float(max_age_s),
                               side="left")) + 1


class PolicyEngine:
    """Evaluates ``rules`` against a ``HierarchyIndex`` (rollup route)
    with a brute-force scan over ``primary.live()`` as the fallback
    when the tree is absent or inexact — same verdicts either way,
    just O(namespace) instead of O(changed). ``aggregate`` serves
    uid_quota totals when attached (an AggregateIndex); without it
    uid totals come from the scan with the same int64 quantization
    the rollup tree uses."""

    def __init__(self, rules, hierarchy=None, aggregate=None,
                 primary=None, max_events: int = 1024, telemetry=None):
        rules = list(rules)
        names = [r.name for r in rules]
        if len(set(names)) != len(names):
            raise ValueError("rule names must be unique")
        self.rules = rules
        self.hierarchy = hierarchy
        self.aggregate = aggregate
        self.primary = primary
        self._lock = threading.RLock()
        #: rule name -> current violation detail (level state)
        self.active: Dict[str, Dict] = {}
        self.events: deque = deque(maxlen=int(max_events))
        self._marks: Dict[str, tuple] = {}
        self._verdict: Dict[str, bool] = {}
        self._last_watermark: Optional[int] = None
        self.stats = {"sweeps": 0, "evaluated": 0, "skipped": 0,
                      "enter": 0, "exit": 0}
        self.telemetry = _resolve_tel(telemetry)
        self._h_sweep_s = self.telemetry.histogram(
            "policy_sweep_seconds", "one incremental evaluate() sweep")
        self._g_violations = self.telemetry.gauge(
            "policy_violations_active", "rules currently in violation")
        self._c_edges = self.telemetry.counter(
            "policy_edges_total", "violation enter/exit transitions",
            labels=("edge",))

    # -- evaluation -----------------------------------------------------------

    def _summary(self, path: str) -> Dict:
        h = self.hierarchy
        if h is not None and h.exact:
            return h.subtree_summary(path)
        if self.primary is None:
            raise RuntimeError(
                "policy engine has no exact hierarchy and no primary "
                "index to scan — attach one or the other")
        return hier.subtree_summary_scan(self.primary.live(), path)

    def _uid_bytes(self, uid: int) -> int:
        if self.aggregate is not None:
            rec = self.aggregate.records.get(f"user:{int(uid)}")
            return int(rec["size"]["total"]) if rec else 0
        if self.primary is None:
            raise RuntimeError(
                "uid_quota rule needs an aggregate or primary index")
        live = self.primary.live()
        typ = live.get("type")
        sel = np.asarray(live["uid"]) == int(uid)
        if typ is not None:
            sel &= np.asarray(typ) != hier.TYPE_DIR
        return int(np.sum(hier.size_bytes_i64(
            np.asarray(live["size"], np.float64)[sel])))

    def _judge(self, rule: Rule) -> Optional[Dict]:
        """Violation detail when ``rule`` is violated, else None."""
        if rule.kind == "max_bytes":
            s = self._summary(rule.path)
            if s["total_bytes"] > rule.limit_bytes:
                return {"total_bytes": s["total_bytes"],
                        "limit_bytes": int(rule.limit_bytes)}
            return None
        if rule.kind == "retention":
            s = self._summary(rule.path)
            mb = retention_min_bucket(rule.max_age_s)
            over_n = sum(s["atime_histogram"]["counts"][mb:])
            if over_n > 0:
                return {"files_over_age": int(over_n),
                        "bytes_over_age":
                            int(sum(s["atime_histogram"]["bytes"][mb:])),
                        "max_age_s": float(rule.max_age_s)}
            return None
        used = self._uid_bytes(rule.uid)
        if used > rule.limit_bytes:
            return {"uid": int(rule.uid), "used_bytes": int(used),
                    "limit_bytes": int(rule.limit_bytes)}
        return None

    def _gate(self, rule: Rule, watermark) -> bool:
        """True when the rule's last verdict provably still stands.
        Subtree rules key on the rollup change mark; uid rules on the
        watermark (aggregate totals move without subtree changes)."""
        if rule.name not in self._verdict:
            return False                 # never judged: must evaluate
        if rule.kind == "uid_quota":
            return (watermark is not None
                    and watermark == self._last_watermark)
        h = self.hierarchy
        if h is None or not h.exact:
            return False                 # scan route: nothing to gate on
        mark = h.change_mark(rule.path)
        return mark == self._marks.get(rule.name)

    def evaluate(self, watermark=None) -> List[Dict]:
        """One incremental sweep: judge every rule whose inputs may
        have moved since the last sweep, keep prior verdicts for the
        rest, and return the edge events this sweep emitted.
        ``watermark`` is any monotone token of applied ingest state
        (e.g. ``freshness()['applied_seq']``); None disables the
        uid-rule gate (they re-evaluate every sweep)."""
        t0 = self.telemetry.clock()
        with self._lock:
            out: List[Dict] = []
            wm = None if watermark is None else int(watermark)
            for rule in self.rules:
                if self._gate(rule, wm):
                    self.stats["skipped"] += 1
                    continue
                # mark BEFORE judging: ops landing mid-judge then leave
                # an unequal mark, so the next sweep re-evaluates
                # (conservative — never skips a changed subtree)
                h = self.hierarchy
                if rule.kind != "uid_quota" and h is not None and h.exact:
                    self._marks[rule.name] = h.change_mark(rule.path)
                detail = self._judge(rule)
                self.stats["evaluated"] += 1
                was = self._verdict.get(rule.name, False)
                now_v = detail is not None
                self._verdict[rule.name] = now_v
                if now_v:
                    self.active[rule.name] = detail
                elif rule.name in self.active:
                    del self.active[rule.name]
                if now_v != was:
                    edge = "enter" if now_v else "exit"
                    ev = {"rule": rule.name, "kind": rule.kind,
                          "edge": edge, "watermark": wm,
                          "detail": detail}
                    self.events.append(ev)
                    self.stats[edge] += 1
                    self._c_edges.labels(edge).inc()
                    out.append(ev)
            self._last_watermark = wm
            self.stats["sweeps"] += 1
            self._g_violations.set(len(self.active))
            self._h_sweep_s.observe(self.telemetry.clock() - t0)
            return out

    def violations(self) -> Dict[str, Dict]:
        """Current level state: rule name -> violation detail."""
        with self._lock:
            return dict(self.active)

    def drain_events(self) -> List[Dict]:
        """Pop every undelivered edge event (oldest first)."""
        with self._lock:
            out = list(self.events)
            self.events.clear()
            return out

    def freshness(self) -> Dict:
        """Monitor-facing marks (joined into dashboard freshness)."""
        with self._lock:
            return {
                "rules": len(self.rules),
                "violations": len(self.active),
                "sweeps": self.stats["sweeps"],
                "evaluated": self.stats["evaluated"],
                "skipped": self.stats["skipped"],
            }

    # -- the Robinhood-style baseline ------------------------------------------

    def full_scan_baseline(self) -> Dict[str, bool]:
        """Judge every rule by brute force over ``primary.live()``,
        ignoring the rollup tree and all gating — the periodic
        full-namespace sweep this engine exists to replace. Returns
        rule name -> violated; the tests check that it agrees with the
        incremental verdicts."""
        if self.primary is None:
            raise RuntimeError("full_scan_baseline needs a primary index")
        live = self.primary.live()
        out: Dict[str, bool] = {}
        for rule in self.rules:
            if rule.kind == "uid_quota":
                typ = live.get("type")
                sel = np.asarray(live["uid"]) == int(rule.uid)
                if typ is not None:
                    sel &= np.asarray(typ) != hier.TYPE_DIR
                used = int(np.sum(hier.size_bytes_i64(
                    np.asarray(live["size"], np.float64)[sel])))
                out[rule.name] = used > rule.limit_bytes
                continue
            s = hier.subtree_summary_scan(live, rule.path)
            if rule.kind == "max_bytes":
                out[rule.name] = s["total_bytes"] > rule.limit_bytes
            else:
                mb = retention_min_bucket(rule.max_age_s)
                out[rule.name] = \
                    sum(s["atime_histogram"]["counts"][mb:]) > 0
        return out

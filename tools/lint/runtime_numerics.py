"""Runtime numerics sanitizer: the dynamic half of the ``num-*`` rules.

``NumericsSanitizer`` records, for tagged values ("sites"), the
**observed dtype** and a sampled **finite-ness gauge**
(``jnp.isfinite`` reduction) every ``interval``-th check.  The contract
mirrors the PR-6 HBM and PR-7 lock-order cross-checks:

* ``assert_all_finite()`` — no tagged value ever held a NaN/inf
  (``first_nonfinite`` names the first offending (step, site));
* ``assert_no_dtype_drift()`` — every site kept ONE dtype across the
  run.  A drift is a live implicit promotion: exactly the class
  ``pin_update_dtypes`` exists to prevent (a bf16 carry silently
  rewritten f32 doubles HBM traffic from that step on);
* ``assert_master_fp32()`` — sites tagged ``role="master"`` observed
  ``float32``, the multi_precision contract ``num-master-dtype``
  checks statically;
* ``assert_consistent_with(flow)`` — observed dtypes match the static
  dtype-flow table (:func:`tools.lint.numerics.static_dtype_flow`):
  a site named ``"<relpath>:<qualname>:<var>"`` whose static entry is
  concrete must observe exactly that dtype.  If the runtime ever
  witnesses a dtype the analyzer derived differently, either the code
  grew an unmodeled conversion or the analyzer regressed.

Each site's first observation — and any later dtype change or
non-finite count — is journaled as a ``numerics/observed`` telemetry
event (per-leaf finite counts + observed dtype, rendered by
``tools/parse_log.py --jsonl``).  ``attach(trainer)`` installs a
telemetry step hook that sweeps the trainer's params, grads and (under
``multi_precision``) fp32 master leaves — including the live ZeRO
sharded mirror — every ``interval`` steps.

Usage::

    from tools.lint.runtime_numerics import NumericsSanitizer
    from tools.lint.numerics import static_dtype_flow

    san = NumericsSanitizer(interval=2).attach(trainer)
    ...train...
    san.detach()
    san.assert_all_finite()
    san.assert_no_dtype_drift()
    san.assert_master_fp32()
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

__all__ = ["NumericsSanitizer"]


def _is_inexact(dtype) -> bool:
    # NOT dtype.kind: ml_dtypes registers bfloat16 with kind 'V'
    import jax.numpy as jnp
    try:
        return bool(jnp.issubdtype(dtype, jnp.inexact))
    except TypeError:
        return False


def _unwrap(value):
    data = getattr(value, "_data", None)
    return data if data is not None else value


class NumericsSanitizer:
    """Observed-dtype journal + sampled finite-ness gauges for tagged
    param/grad/state leaves (see module docstring for the contract)."""

    def __init__(self, interval: int = 1, telemetry_events: bool = True):
        self.interval = max(1, int(interval))
        self.telemetry_events = telemetry_events
        # site -> {"dtypes": [..in observation order..], "checks": int,
        #          "nonfinite": int, "role": str|None}
        self.observed: Dict[str, dict] = {}
        self.first_nonfinite: Optional[Tuple[Optional[int], str]] = None
        self._hook = None
        self._attached: List[object] = []
        self._steps = 0

    # -- recording ------------------------------------------------------
    def observe(self, site: str, value, role: Optional[str] = None,
                step: Optional[int] = None):
        """Record one observation of ``value`` at ``site``.  Floating
        leaves get a finite-ness reduction (one device sync); integer
        leaves record dtype only."""
        import jax.numpy as jnp
        arr = _unwrap(value)
        if getattr(arr, "is_deleted", lambda: False)():
            # a donated buffer (Trainer(donate_grads=True)): the update
            # consumed it and there is nothing left to read.  Executing on
            # it raises — and on the multi-device CPU backend of jaxlib
            # 0.9.0 the failed launch then hangs the next sharded op.
            return
        dt = str(arr.dtype)
        bad = 0
        if _is_inexact(arr.dtype):
            bad = int(arr.size - int(jnp.isfinite(arr).sum()))
        rec = self.observed.get(site)
        fresh = rec is None
        if fresh:
            rec = self.observed[site] = {"dtypes": [], "checks": 0,
                                         "nonfinite": 0, "role": role}
        drift = bool(rec["dtypes"]) and dt not in rec["dtypes"]
        if fresh or drift:
            rec["dtypes"].append(dt)
        rec["checks"] += 1
        rec["nonfinite"] += bad
        if bad and self.first_nonfinite is None:
            self.first_nonfinite = (step, site)
        if (fresh or drift or bad) and self.telemetry_events:
            try:
                from mxnet_tpu import telemetry
                telemetry.event("numerics", "observed", leaf=site,
                                dtype=dt, nonfinite=bad,
                                size=int(arr.size), step=step,
                                role=role,
                                drift=rec["dtypes"] if drift else None)
            except Exception:
                pass
        return rec

    # -- trainer sweep --------------------------------------------------
    def _sweep_trainer(self, trainer, step):
        optimizer = getattr(trainer, "_optimizer", None)
        mp = bool(getattr(optimizer, "multi_precision", False))
        # the live ZeRO sharded mirror shadows the updater's
        # natural-shape states; its leaf 0 IS the master under mp
        mirror = {}
        for f in (getattr(trainer, "_kv_fused", None),
                  getattr(trainer, "_local_fused", None)):
            if f is not None:
                mirror.update(getattr(f, "_sharded", {}))
        updater = None
        if getattr(trainer, "_update_on_kvstore", False):
            updater = getattr(getattr(trainer, "_kvstore", None),
                              "_updater", None)
        if updater is None:
            updater = getattr(trainer, "_updaters", None)
        if isinstance(updater, (list, tuple)):
            updater = updater[0] if updater else None
        states = getattr(updater, "states", {}) if updater is not None \
            else {}
        import numpy as onp
        for i, p in enumerate(getattr(trainer, "_params", [])):
            if p._data is None:
                continue
            self.observe("param:%s" % p.name, p.data(), role="param",
                         step=step)
            if p.grad_req != "null" and p._grad is not None:
                self.observe("grad:%s" % p.name, p.grad(), role="grad",
                             step=step)
            if mp and onp.dtype(p.dtype).itemsize < 4:
                master = None
                if i in mirror and mirror[i]:
                    master = mirror[i][0]
                else:
                    st = states.get(i)
                    if isinstance(st, (tuple, list)) and st:
                        master = st[0]
                if master is not None:
                    self.observe("master:%s" % p.name, master,
                                 role="master", step=step)

    def attach(self, trainer):
        """Sweep ``trainer``'s params/grads/masters from the telemetry
        step hook every ``interval``-th step (the Monitor.attach
        pattern — no training-loop plumbing).  Returns ``self``."""
        from mxnet_tpu import telemetry
        if trainer not in self._attached:
            self._attached.append(trainer)
        if self._hook is None:
            def _hook(rec):
                if rec.get("source") != "trainer" or \
                        rec.get("owner") not in self._attached:
                    return
                self._steps += 1
                if (self._steps - 1) % self.interval:
                    return
                self._sweep_trainer(rec["owner"], rec.get("index"))
            self._hook = telemetry.add_step_hook(_hook)
        return self

    def detach(self):
        if self._hook is not None:
            from mxnet_tpu import telemetry
            telemetry.remove_step_hook(self._hook)
            self._hook = None
        self._attached = []

    # -- queries / assertions -------------------------------------------
    def dtypes(self) -> Dict[str, str]:
        """site -> first observed dtype."""
        return {s: r["dtypes"][0] for s, r in self.observed.items()
                if r["dtypes"]}

    @staticmethod
    def _contract_failed(contract: str, msg: str):
        """A violated runtime contract is an incident: freeze the
        flight-recorder bundle (journal tail holds the
        ``numerics/observed`` events that narrate the drift) BEFORE
        raising, so the postmortem survives the test/process dying on
        the AssertionError."""
        try:
            from mxnet_tpu import flight_recorder
            flight_recorder.dump_incident("numerics_%s" % contract,
                                          detail=msg)
        except Exception:       # recorder trouble must not mask the bug
            pass
        raise AssertionError(msg)

    def assert_all_finite(self):
        bad = {s: r["nonfinite"] for s, r in self.observed.items()
               if r["nonfinite"]}
        if bad:
            self._contract_failed("nonfinite", (
                "runtime numerics: non-finite values observed (first at "
                "step %s in %r):\n  "
                % (self.first_nonfinite or (None, "?"))
                + "\n  ".join("%s: %d non-finite" % kv
                              for kv in sorted(bad.items()))))

    def assert_no_dtype_drift(self):
        drifted = {s: r["dtypes"] for s, r in self.observed.items()
                   if len(r["dtypes"]) > 1}
        if drifted:
            self._contract_failed("dtype_drift", (
                "runtime numerics: observed dtype drift (a live "
                "implicit promotion — the static complement is "
                "num-implicit-promotion):\n  "
                + "\n  ".join("%s: %s" % (s, " -> ".join(d))
                              for s, d in sorted(drifted.items()))))

    def assert_master_fp32(self):
        bad = {s: r["dtypes"] for s, r in self.observed.items()
               if r.get("role") == "master"
               and r["dtypes"] != ["float32"]}
        if bad:
            self._contract_failed("master_dtype", (
                "runtime numerics: fp32 master leaves observed "
                "off-float32 (num-master-dtype contract):\n  "
                + "\n  ".join("%s: %s" % (s, d)
                              for s, d in sorted(bad.items()))))

    def assert_consistent_with(self, flow: dict):
        """Every observed site named ``"<relpath>:<qualname>:<var>"``
        whose variable has a concrete entry in ``flow`` (a
        :func:`tools.lint.numerics.static_dtype_flow` table) must have
        observed exactly that dtype."""
        mismatches = []
        for site, rec in sorted(self.observed.items()):
            fn_key, _, var = site.rpartition(":")
            expect = flow.get(fn_key, {}).get(var)
            if expect is None:
                continue
            if rec["dtypes"] != [expect]:
                mismatches.append((site, expect, rec["dtypes"]))
        if mismatches:
            self._contract_failed("flow_mismatch", (
                "runtime numerics: observed dtypes diverge from the "
                "static dtype-flow table (unmodeled conversion or "
                "analyzer regression):\n  "
                + "\n  ".join("%s: static %s, observed %s" % m
                              for m in mismatches)))

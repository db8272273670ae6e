"""Compiled-cost registry: FLOPs/bytes/temp-memory per jitted entry
point, captured at mint time (ISSUE 15).

The compile-contract registry (analysis/contracts.py) already knows
WHICH executables exist; this module records what each one COSTS —
`cost_analysis()` FLOPs and bytes-accessed from the lowering, plus
`memory_analysis()` temp/argument bytes from the compile — keyed by
contract name + specialization key, so the runtime can answer "what
device work does one dispatch of this executable represent" without a
profiler attached. Consumers:

- the trainer's goodput ledger turns the train.step record into a live
  MFU gauge (registry FLOPs x productive steps / wall / chipspec peak)
  and a per-executable achieved-GB/s roofline gauge;
- the engine's dispatch-overhead gauge compares each round's modeled
  device seconds (the record's roofline time on the detected chip)
  against the measured round wall;
- `tools/graft_check.py costs` diffs the audit's per-contract FLOPs and
  temp bytes against a checked-in baseline so a silent 2x FLOPs
  regression in any jitted entry point fails CI loudly.

The capture contract (GR006-enforced): capture happens at MINT time
only — once per (contract, specialization), never in the per-round /
per-step hot loop. `attach()` hooks the contract registry's mint
listener so the pending inventory mirrors record_variant exactly; the
owner (engine, trainer) then calls `capture()` with example args at the
same mint site. The hot loop only ever calls `record()` /
`CostRecord.modeled_seconds` — pure dict lookups and host arithmetic,
listed in graft-check GR006 HOT_PATHS.

Capture cost: `fn.lower(*args)` is an abstract trace (no XLA compile)
and yields cost_analysis; `capture_memory=True` additionally compiles
the lowering for memory_analysis. On jax 0.9.0 that compile and the jit
call share one executable cache (checked: a jit call after
`.lower().compile()` does not compile again), so capture costs a
retrace per minted executable, not a second XLA compile. The registry
stays opt-in (`--device_cost_registry`, engine `cost_registry=True`),
like the trainer's --log_memory_to_tensorboard relower.
"""

from __future__ import annotations

import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from megatron_llm_tpu.analysis import contracts as _contracts

__all__ = ["CostRecord", "CostRegistry"]


def _key_str(key: Any) -> str:
    return repr(key)


@dataclass
class CostRecord:
    """The captured device-cost facts of ONE minted executable."""

    contract: str
    key: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    temp_bytes: Optional[int] = None
    arg_bytes: Optional[int] = None
    source: str = "lowered"  # "lowered" | "compiled"
    captured_unix: float = field(default_factory=time.time)

    def modeled_seconds(self, chip, n_chips: int = 1,
                        dtype: str = "bf16") -> Optional[float]:
        """Roofline device time for one execution on `chip`
        (telemetry/chipspec.ChipSpec): max of the compute leg
        (flops / peak) and the memory leg (bytes / HBM rate), across
        `n_chips` chips. None when the record or chip cannot support
        the estimate — callers drop their gauge instead of guessing.
        GR006 HOT_PATHS: pure host arithmetic (the engine calls this
        per round)."""
        if chip is None:
            return None
        legs = []
        if self.flops:
            legs.append(self.flops / (chip.peak_flops_for(dtype)
                                      * max(n_chips, 1)))
        if self.bytes_accessed:
            legs.append(self.bytes_accessed / (chip.hbm_bytes_s
                                               * max(n_chips, 1)))
        return max(legs) if legs else None

    def to_dict(self) -> dict:
        return {
            "contract": self.contract, "key": self.key,
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes, "arg_bytes": self.arg_bytes,
            "source": self.source,
        }


def _analysis_dict(analysis) -> dict:
    """cost_analysis() is a dict, or None where the backend has none."""
    return dict(analysis or {})


class CostRegistry:
    """Mint-time cost capture keyed by (contract, specialization).

    `owner`: when set, the mint listener only tracks variants minted
    under that contract owner (an engine instance tracks its own mints,
    not a sibling replica's); None tracks every mint.
    """

    def __init__(self, chip=None, capture_memory: bool = True,
                 owner: Any = None):
        self.chip = chip
        self.capture_memory = capture_memory
        self._owner_ref = (weakref.ref(owner) if owner is not None
                           else None)
        self._lock = threading.Lock()
        self._records: Dict[tuple, CostRecord] = {}
        # mint inventory from the contracts hook: every (name, key)
        # record_variant accepted, whether or not costs are captured
        # yet — the "registry knows what exists" half of the story
        self._pending: Dict[tuple, float] = {}
        self._listener = None
        self.captures = 0
        self.capture_errors = 0

    # -- the record_variant hook (mint-time inventory) ---------------------

    def attach(self) -> "CostRegistry":
        """Install the mint listener on analysis/contracts.py: every
        NEW variant record_variant accepts lands in the pending
        inventory. Idempotent; the listener holds only a weakref to
        this registry so a dropped registry never pins itself alive
        through the module-global listener list."""
        if self._listener is not None:
            return self
        ref = weakref.ref(self)
        owner_ref = self._owner_ref

        def _on_mint(name, key, owner, _ref=ref, _owner_ref=owner_ref):
            reg = _ref()
            if reg is None:
                # the registry (and its engine/trainer) died without
                # detach(): remove THIS closure from the module-global
                # listener list so cycled owners can never accumulate
                # dead entries (a long-lived process restarting replica
                # fleets would otherwise leak one per registry)
                _contracts.remove_mint_listener(_on_mint)
                return
            if _owner_ref is not None and owner is not _owner_ref():
                return
            reg.note_mint(name, key)

        self._listener = _on_mint
        _contracts.add_mint_listener(_on_mint)
        return self

    def detach(self) -> None:
        if self._listener is not None:
            _contracts.remove_mint_listener(self._listener)
            self._listener = None

    def note_mint(self, name: str, key: Any) -> None:
        with self._lock:
            self._pending.setdefault((name, _key_str(key)), time.time())

    # -- capture (mint-time only — never per-round) ------------------------

    def capture(self, name: str, key: Any, fn, args: tuple,
                kwargs: Optional[dict] = None) -> Optional[CostRecord]:
        """Capture the cost facts of one minted executable from its
        jitted fn + example args. The lowering is an abstract trace
        (cheap); with capture_memory the compile for memory_analysis is
        one EXTRA full compile (module docstring) — both are mint-time
        one-offs. Errors are swallowed into `capture_errors`: cost
        observability must never take a mint down."""
        try:
            lowered = fn.lower(*args, **(kwargs or {}))
            rec = CostRecord(contract=name, key=_key_str(key))
            try:
                ca = _analysis_dict(lowered.cost_analysis())
                rec.flops = float(ca["flops"]) if "flops" in ca else None
                if "bytes accessed" in ca:
                    rec.bytes_accessed = float(ca["bytes accessed"])
            except Exception:  # noqa: BLE001 — backend without analysis
                pass
            if self.capture_memory:
                compiled = lowered.compile()
                rec.source = "compiled"
                try:
                    mem = compiled.memory_analysis()
                    rec.temp_bytes = int(mem.temp_size_in_bytes)
                    rec.arg_bytes = int(mem.argument_size_in_bytes)
                except Exception:  # noqa: BLE001
                    pass
                if rec.flops is None:
                    ca = _analysis_dict(compiled.cost_analysis())
                    rec.flops = (float(ca["flops"])
                                 if "flops" in ca else None)
                    if "bytes accessed" in ca:
                        rec.bytes_accessed = float(ca["bytes accessed"])
        except Exception:  # noqa: BLE001
            with self._lock:
                self.capture_errors += 1
            return None
        return self._store(rec)

    def capture_compiled(self, name: str, key: Any,
                         compiled) -> Optional[CostRecord]:
        """Capture from an already-compiled artifact (the audit and the
        trainer's step-0 relower hold one) — no extra compile."""
        rec = CostRecord(contract=name, key=_key_str(key),
                         source="compiled")
        try:
            ca = _analysis_dict(compiled.cost_analysis())
            rec.flops = float(ca["flops"]) if "flops" in ca else None
            if "bytes accessed" in ca:
                rec.bytes_accessed = float(ca["bytes accessed"])
            mem = compiled.memory_analysis()
            rec.temp_bytes = int(mem.temp_size_in_bytes)
            rec.arg_bytes = int(mem.argument_size_in_bytes)
        except Exception:  # noqa: BLE001 — partial facts still useful
            pass
        return self._store(rec)

    def _store(self, rec: CostRecord) -> CostRecord:
        with self._lock:
            self._records[(rec.contract, rec.key)] = rec
            self._pending.pop((rec.contract, rec.key), None)
            self.captures += 1
        return rec

    # -- hot-loop reads (GR006 HOT_PATHS: host lookups only) ---------------

    def record(self, name: str, key: Any = None) -> Optional[CostRecord]:
        """The record for (contract, specialization); with key=None,
        any record under the contract (single-specialization
        contracts). Pure dict lookup — the engine's per-round
        dispatch-overhead accounting calls this."""
        if key is not None:
            return self._records.get((name, _key_str(key)))
        for (n, _k), rec in self._records.items():
            if n == name:
                return rec
        return None

    # -- export ------------------------------------------------------------

    def rows(self) -> List[dict]:
        with self._lock:
            recs = sorted(self._records.values(),
                          key=lambda r: (r.contract, r.key))
            pending = sorted(k for k in self._pending)
        out = [r.to_dict() for r in recs]
        out.extend({"contract": n, "key": k, "pending": True}
                   for n, k in pending)
        return out

    def snapshot(self) -> dict:
        """Flight-recorder / /metrics attachment: the whole table plus
        capture health."""
        return {
            "chip": self.chip.label() if self.chip else None,
            "captures": self.captures,
            "capture_errors": self.capture_errors,
            "records": self.rows(),
        }

    def prometheus_lines(self, prefix: str = "") -> List[str]:
        """Labeled Prometheus gauges for the /metrics text exposition:
        one sample per (contract, specialization) per fact — the
        labeled form a scraper can alert on per entry point."""
        metrics = (("cost_flops", "flops"),
                   ("cost_bytes_accessed", "bytes_accessed"),
                   ("cost_temp_bytes", "temp_bytes"),
                   ("cost_arg_bytes", "arg_bytes"))
        with self._lock:
            recs = sorted(self._records.values(),
                          key=lambda r: (r.contract, r.key))
        lines: List[str] = []
        for mname, attr in metrics:
            samples = []
            for r in recs:
                v = getattr(r, attr)
                if v is None:
                    continue
                key = r.key.replace("\\", "\\\\").replace('"', '\\"')
                samples.append(
                    f'{prefix}{mname}{{contract="{r.contract}",'
                    f'key="{key}"}} {v:g}')
            if samples:
                lines.append(f"# TYPE {prefix}{mname} gauge")
                lines.extend(samples)
        return lines

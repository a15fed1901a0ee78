"""oslint — AST-based host/device discipline linter for opensearch_tpu.

Four checkers tailored to this repo's failure modes (see
docs/STATIC_ANALYSIS.md for rationale and the round-5 review's lineage):

- OSL101/OSL102 dtype-discipline (`dtype_rules`): float domain mixing in
  score comparisons; float-rounded count planes.
- OSL201/OSL202/OSL203 jit-boundary (`jit_rules`): traced-value branches,
  host syncs, nondeterminism inside jit/shard_map/Pallas code.
- OSL301 breaker-discipline (`breaker_rules`): ndocs-scale host caches
  without a memory-breaker charge/release.
- OSL401/OSL402 lock-discipline (`lock_rules`): attributes mutated both
  under and outside a lock; lock-order inversions.
- OSL501/OSL502 telemetry-discipline (`telemetry_rules`): wall-clock
  duration subtraction; module-level counter-dict `+=` in hot paths.
- OSL503 wait-discipline (`lock_rules`): sleep-polling loops in serving
  hot paths.
- OSL504 device-sync discipline (`sync_rules`): blocking device syncs
  (`jax.device_get`, `block_until_ready`, device-named `np.asarray`)
  inside launch-stage code — the static guard on the pipelined
  launch/fetch split (docs/SERVING.md).
- OSL505 recorder/slowlog emission discipline (`recorder_rules`).
- OSL506 memory-accounting discipline (`memory_rules`): direct breaker
  `add_estimate`/`release` outside the HBM ledger; `jax.device_put`
  residency in index/search/parallel without a ledger registration in
  the enclosing scope.
- OSL508 RPC-path discipline (`rpc_rules`): no unbounded wire calls and
  no silently-swallowed transport errors in `cluster/`.
- OSL507 quantized-impact domain discipline (`impact_rules`): u8/u16
  impact planes enter f32 score math only through the designated
  dequant helpers; codec-version branches in search/ consult
  Segment.codec_version and use the named codec constants.
- OSL603 actuator discipline (`actuator_rules`): every
  remediation/shed/deprioritize engage site in serving/ or cluster/
  carries a paired release path or TTL bound in file — bounded,
  reversible actions only (docs/RESILIENCE.md "Self-healing loop").
- OSL604 fusion score-domain discipline (`fusion_rules`): linear
  combinations of sub-query scores in fusion-shaped functions pass
  through a designated normalizer (fusion.normalize_scores) or fuse in
  the rank domain (RRF) — raw BM25/cosine/sparse-dot scores are
  incomparable (docs/HYBRID.md).
- OSL605 write-path emission discipline (`ingest_obs_rules`):
  wall-clock duration subtraction / in-loop `time.time()`,
  per-iteration metric-registry emission, and unguarded recorder
  events in `index/` + `ingest/` — the ingest observatory's contract
  that hot modules call one guarded helper (docs/OBSERVABILITY.md
  "Ingest observatory").
- OSL701-OSL704 whole-program concurrency suite (`concurrency/`):
  unlike every rule above, these run INTERPROCEDURALLY over the full
  package — a lock inventory with alias resolution, a call-graph walk
  of lock regions, and fixpoint may-acquire/may-block summaries.
  OSL701 lock-order cycles (potential deadlock) + non-reentrant
  re-acquire; OSL702 locks held across blocking ops (RPC sends, device
  syncs, sleeps, foreign waits); OSL703 cross-thread unlocked attribute
  writes; OSL704 check-then-act atomicity splits. The derived
  lock-order graph is committed as `lock_order.json` (ratcheted by
  tier-1) and validated at runtime by devtools/lockwitness.py.

Run via `python scripts/oslint.py [--check]`; tier-1 runs it through
tests/test_oslint.py. Suppress inline with
`# oslint: disable=RULE -- justification`, or triage pre-existing debt in
the checked-in `oslint_baseline.json`.
"""

from .actuator_rules import ActuatorDisciplineChecker
from .breaker_rules import BreakerDisciplineChecker
from .concurrency import (CONCURRENCY_RULES, build_lock_order,
                          build_program, diff_lock_order,
                          run_program_scope)
from .core import (Baseline, Checker, Finding, default_checkers,
                   load_baseline, run_paths, run_source, write_baseline)
from .dtype_rules import DtypeDisciplineChecker
from .fusion_rules import FusionDomainChecker
from .impact_rules import ImpactDomainChecker
from .ingest_obs_rules import IngestObsDisciplineChecker
from .insights_rules import InsightsCardinalityChecker
from .jit_rules import JitBoundaryChecker
from .lock_rules import LockDisciplineChecker
from .memory_rules import MemoryAccountingChecker
from .sync_rules import DeviceSyncDisciplineChecker

__all__ = [
    "Baseline", "Checker", "Finding", "default_checkers", "load_baseline",
    "run_paths", "run_source", "write_baseline",
    "DtypeDisciplineChecker", "FusionDomainChecker",
    "JitBoundaryChecker",
    "BreakerDisciplineChecker", "LockDisciplineChecker",
    "DeviceSyncDisciplineChecker", "MemoryAccountingChecker",
    "ImpactDomainChecker", "IngestObsDisciplineChecker",
    "InsightsCardinalityChecker",
    "ActuatorDisciplineChecker",
    "CONCURRENCY_RULES", "build_lock_order", "build_program",
    "diff_lock_order", "run_program_scope",
]

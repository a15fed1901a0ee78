"""Observability forensics: the flight recorder (per-request black-box
event journal with anomaly-triggered dumps), the hot-threads stack
sampler, the HBM ledger (attributed device-memory accounting, the sole
breaker-charge path — oslint OSL506), per-query device cost accounting
(predicted vs. actual bytes gathered), the time-series retention ring
(`timeseries.py` — bounded periodic registry snapshots behind
`_nodes/stats/history`, oslint OSL509), and the SLO burn-rate engine
(`slo.py` — declared objectives over sliding windows, `GET /_slo`).
docs/OBSERVABILITY.md documents the event schema, dump triggers, tenant
catalogue, cost-model formulas, and the fleet/SLO model."""

from .flight_recorder import (FlightRecorder, RECORDER, current,
                              reset_current, set_current)
from .hbm_ledger import LEDGER, HBMLedger
from .hot_threads import hot_threads
from .slo import SLO, SLO_ENGINE, SLOEngine, default_slos, ingest_slos
from .timeseries import SAMPLER, TimeSeriesSampler

__all__ = ["FlightRecorder", "RECORDER", "current", "set_current",
           "reset_current", "hot_threads", "LEDGER", "HBMLedger",
           "SAMPLER", "TimeSeriesSampler", "SLO", "SLOEngine",
           "SLO_ENGINE", "default_slos", "ingest_slos"]

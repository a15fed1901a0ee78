"""Circuit breakers: HBM budget accounting. Analog of reference
`indices/breaker/HierarchyCircuitBreakerService.java` — instead of JVM heap,
we budget device HBM for segment residency and reject loads that would
exceed the limit.

Charge discipline (oslint OSL506): product code never calls
`add_estimate`/`release` directly — every HBM tenant registers an
attributed allocation with the ledger (`obs/hbm_ledger.py`), which
derives the breaker charge and guarantees the standing invariant
`sum(live charged ledger bytes) == breaker.used`."""

from __future__ import annotations


class CircuitBreakingException(Exception):
    """HTTP 429 analog (reference CircuitBreakingException)."""


class CircuitBreaker:
    def __init__(self, name: str, limit_bytes: int):
        self.name = name
        self.limit = limit_bytes
        self.used = 0
        self.trip_count = 0

    def add_estimate(self, bytes_: int, label: str = "") -> None:
        if self.used + bytes_ > self.limit:
            self.trip_count += 1
            raise CircuitBreakingException(
                f"[{self.name}] Data too large, data for [{label}] would be "
                f"[{self.used + bytes_}/{self.limit}] bytes")
        self.used += bytes_

    def release(self, bytes_: int) -> None:
        self.used = max(0, self.used - bytes_)

    def stats(self) -> dict:
        return {"limit_size_in_bytes": self.limit, "estimated_size_in_bytes": self.used,
                "tripped": self.trip_count}


class BreakerService:
    def __init__(self, device_limit_bytes: int = 16 << 30):
        # v5e has 16 GiB HBM. fielddata covers every device-resident
        # layout (the fastpath's aligned postings + filter-specialized
        # copies, the segments' columns, the vector matrices and the IVF
        # rows in list order), the dominant HBM tenant — give it three
        # quarters (12 GiB: a shard of 2M vectors of 768 floats with its
        # list-ordered copy is 12.65 GB), the rest is scratch + compiled
        # programs (reference fielddata default is 40% of a JVM heap; HBM
        # residency is this engine's whole design)
        self.breakers = {
            "fielddata": CircuitBreaker("fielddata",
                                        device_limit_bytes * 3 // 4),
            "request": CircuitBreaker("request", device_limit_bytes // 3),
            "parent": CircuitBreaker("parent", device_limit_bytes),
        }

    def breaker(self, name: str) -> CircuitBreaker:
        return self.breakers[name]

    def stats(self) -> dict:
        return {k: v.stats() for k, v in self.breakers.items()}

"""The environment options PR 46 retired (`docs/OPTIONS.md`, "Retired") are
the values their absence gave, and setting one moves nothing: each case sets
the retired variable to another value and reads the default. Every case
fails on a tree that still reads the variable."""

import pytest

from opensearch_tpu.cluster.distnode import RetryPolicy
from opensearch_tpu.index.segment import CODEC_V2, default_codec_version
from opensearch_tpu.obs.flight_recorder import FlightRecorder
from opensearch_tpu.obs.insights import QueryInsights
from opensearch_tpu.obs.timeseries import SAMPLER, TimeSeriesSampler
from opensearch_tpu.ops import device_merge
from opensearch_tpu.search import fastpath
from opensearch_tpu.serving.remediator import REMEDIATOR, RemediationConfig
from opensearch_tpu.serving.scheduler import SchedulerConfig

# (retired variable, the value a test sets it to, how the value in use is
# read, the value in use)
CASES = [
    ("FR_CAPACITY", "99", lambda: FlightRecorder().capacity, 4096),
    ("FR_MAX_DUMPS", "3", lambda: FlightRecorder().max_dumps, 16),
    ("FLIGHT_RECORDER", "0", lambda: FlightRecorder().enabled, True),
    ("INSIGHTS_CAPACITY", "7", lambda: QueryInsights().capacity, 256),
    ("INSIGHTS_WINDOW_CAP", "7",
     lambda: QueryInsights().window_capacity, 4096),
    ("INSIGHTS", "0", lambda: QueryInsights().enabled, True),
    ("TS_INTERVAL_S", "9", lambda: TimeSeriesSampler().interval_s, 1.0),
    ("TS_CAPACITY", "9", lambda: TimeSeriesSampler().capacity, 512),
    ("REMEDIATION_TTL_S", "9", lambda: RemediationConfig().ttl_s, 60.0),
    ("REMEDIATION_HOLD_S", "9",
     lambda: RemediationConfig().green_hold_s, 2.0),
    ("REMEDIATION_COOLDOWN_S", "9",
     lambda: RemediationConfig().engage_cooldown_s, 1.0),
    ("REMEDIATION_ADMISSION", "0.9",
     lambda: RemediationConfig().admission_factor, 0.5),
    ("SCHED_MAX_BATCH", "9", lambda: SchedulerConfig().max_batch, 32),
    ("SCHED_MAX_WAIT_US", "9", lambda: SchedulerConfig().max_wait_us, 1000),
    ("SCHED_QUEUE_CAP", "9", lambda: SchedulerConfig().queue_cap, 256),
    ("SCHED_ORACLE", "1", lambda: SchedulerConfig().oracle, False),
    ("PIPELINE_DEPTH", "9", lambda: SchedulerConfig().pipeline_depth, 2),
    ("RPC_RETRIES", "9", lambda: RetryPolicy().same_member_retries, 1),
    ("RETRY_BUDGET", "9", lambda: RetryPolicy().budget, 4),
    ("RETRY_STORM_N", "9", lambda: RetryPolicy().storm_n, 4),
    # off the chip the rescore is the host's, whatever the variable says
    ("RESCORE", "device", fastpath.rescore_mode, "host"),
    ("NO_DEVICE_MERGE", "1",
     lambda: (device_merge.use_device_merge(device_merge.DEVICE_MERGE_MIN),
              device_merge.use_device_impacts(
                  device_merge.DEVICE_IMPACT_MIN)), (True, True)),
    ("CODEC", "1", default_codec_version, CODEC_V2),
]


@pytest.mark.parametrize("name,value,read,in_use", CASES,
                         ids=[c[0] for c in CASES])
def test_a_retired_option_is_its_old_default(monkeypatch, name, value, read,
                                             in_use):
    monkeypatch.setenv(f"OPENSEARCH_TPU_{name}", value)
    assert read() == in_use


@pytest.mark.parametrize("name", ["REMEDIATION", "TS"])
def test_a_node_starts_nothing_by_the_environment(monkeypatch, name):
    """The remediator is armed and the sampler thread started by a call
    (`REMEDIATOR.arm(node=...)`, `SAMPLER.ensure_started()`), not by a
    variable `Node.__init__` reads."""
    from opensearch_tpu.cluster.node import Node
    monkeypatch.setenv(f"OPENSEARCH_TPU_{name}", "1")
    was = (REMEDIATOR.armed, SAMPLER.running)
    Node(mesh_service=False)
    assert (REMEDIATOR.armed, SAMPLER.running) == was

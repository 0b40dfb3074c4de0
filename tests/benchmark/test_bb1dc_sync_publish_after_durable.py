"""The durability case of ``test_bb1dc_sync.py`` under
``Config.publish_after_durable``: a commit's effects publish only once
its tickets are covered, so a 2PC commit holds a deferred publish on
every partition it staged while it redeems them.  A copy of the data
directory taken under load still recovers every acknowledged
transaction."""

import functools

import pytest

from test_bb1dc_sync import (  # noqa: F401 — the fixture
    THREADS,
    TXNS,
    killed_under_load,
    nothing_acknowledged_is_missing,
    on_the_cpu,
)

from antidote_tpu import config as config_module
from antidote_tpu.txn.manager import PartitionManager


@pytest.mark.parametrize("backend", ["auto", "python"])
def test_an_acknowledged_commit_survives_a_kill_publishing_after_durable(
        tmp_path, monkeypatch, backend):
    monkeypatch.setattr(config_module, "Config", functools.partial(
        config_module.Config, publish_after_durable=True))
    deferred = []
    real_redeem = PartitionManager.redeem

    def redeem(self, staged):
        deferred.append(staged.defer)
        real_redeem(self, staged)

    monkeypatch.setattr(PartitionManager, "redeem", redeem)
    got = killed_under_load(tmp_path, monkeypatch, True, backend)
    # every commit ran its publish behind its ticket (two partitions a
    # transaction)
    assert deferred == [True] * (2 * THREADS * TXNS)
    nothing_acknowledged_is_missing(got)
    assert len(got["covered"]) == 2 * THREADS * TXNS
    assert all(got["covered"])
    assert all(got["reached"])

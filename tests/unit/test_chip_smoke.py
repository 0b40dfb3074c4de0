"""chip_smoke.py on the CPU, in seconds: it refuses to run without a
chip; its phases agree with the plain reference at a tiny size; and a
wrong answer or an ERROR log record fails the run."""

import logging
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import chip_smoke  # noqa: E402

#: 2 partitions x 300 keys; phase D needs the chip's compiler
TINY = dict(partitions=2, keys_per_partition=300, reads=40, rmw=10,
            dc_txns=40, dc_keys=400, phases="ABC")


def run_tiny(tmp_path, watch, **changes):
    return chip_smoke.run_phases(
        seed=7, sizes=chip_smoke.Sizes(**{**TINY, **changes}),
        workdir=str(tmp_path), watch=watch)


def test_main_refuses_to_run_without_a_chip(capsys):
    # the suite runs under JAX_PLATFORMS=cpu (tests/conftest.py)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert out.out == ""  # no result line


def test_phases_agree_with_the_plain_reference(tmp_path):
    with chip_smoke.LogWatch() as watch:
        result = run_tiny(tmp_path, watch)
    a = result["A"]
    assert a["keys"] == 600 and a["planes"]["resident_keys"] > 0
    assert a["writes"]["device_flushes"] > 0
    assert result["B"]["sample"] > 0
    assert result["C"]["txns"] == 40
    # at this size the value cache answers every read: the chip proof
    # must refuse such a run
    assert a["reads"]["read_dispatches"] == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="never reached"):
        chip_smoke.chip_proof(a, "one")


def test_a_wrong_answer_fails_the_run(tmp_path, monkeypatch):
    true_value = chip_smoke.PlainStore.value

    def corrupted(self, key, type_name):
        value = true_value(self, key, type_name)
        return value + 1 if key == 5 else value  # key 5 is a counter

    monkeypatch.setattr(chip_smoke.PlainStore, "value", corrupted)
    with chip_smoke.LogWatch() as watch:
        with pytest.raises(chip_smoke.SmokeFailure, match="key 5"):
            # every key is read: 300 reads of 10 over 600 keys miss few,
            # phase B's sample takes the rest
            run_tiny(tmp_path, watch, reads=300, phases="AB")


def test_an_error_log_record_fails_the_run(tmp_path):
    with chip_smoke.LogWatch() as watch:
        logging.getLogger("antidote_tpu.mat.serve").error(
            "fused serve read failed; falling back (injected)")
        with pytest.raises(chip_smoke.SmokeFailure, match="ERROR log"):
            run_tiny(tmp_path, watch, phases="A")


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_dead_thread_fails_the_run():
    import threading

    def boom():
        raise RuntimeError("injected")

    with chip_smoke.LogWatch() as watch:
        t = threading.Thread(target=boom, name="injected-thread")
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive()
        with pytest.raises(chip_smoke.SmokeFailure, match="thread died"):
            watch.check("test")

"""``chip_smoke.py`` on the CPU: its body at a small key count (interpret-
mode kernels), and its refusal to run as a script without a TPU."""

import chip_smoke


def test_smoke_body_matches_reference_on_cpu():
    """Every lane of the warm, YCSB-A, YCSB-E, split-insert and read-back
    batches agrees with the plain reference, leaves split on device and the
    checked window compiles nothing."""
    s = chip_smoke.run(1, 30_000, seed=3, n_batches=1, log=lambda *a: None)
    assert s["mismatches"] == 0
    assert s["leaf_splits_on_device"] >= 1
    assert s["split_lanes"] == chip_smoke.BATCH
    assert s["compiles_run"] == 0
    assert all(s["ops"][k] > 0 for k in ("lookup", "update", "insert",
                                          "scan"))


def test_script_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err

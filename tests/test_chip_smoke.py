"""chip_smoke.py on the CPU harness: its phase functions run train -> score ->
serve at a few thousand rows, ``main()`` refuses a backend that is not a
TPU, and a recovery anywhere on the path fails the smoke's fault check
(on the chip a fallback is a bug, not resilience)."""
import json

import numpy as np
import pytest

import chip_smoke as cs
from transmogrifai_tpu.observability import metrics as obs_metrics
from transmogrifai_tpu.robustness import faults

ROWS, HOLDOUT = 3000, 1000


def test_phases_train_score_serve():
    obs_metrics.enable_metrics(True)   # the conftest fixture resets it
    data = cs.make_data(ROWS + HOLDOUT, seed=3)
    table = cs.table_of(data, 0, ROWS)
    holdout = cs.table_of(data, ROWS, ROWS + HOLDOUT)

    single = cs.phase_train(table)
    # TG_FAST_GRIDS (conftest) shrinks the stock grids: main() would
    # refuse this count, which is the point of its 135-fit check
    assert 0 < single["fits"] < cs.DEFAULT_GRID_FITS
    assert single["results"][
        (single["family"],
         cs.json.dumps(single["hyper"], sort_keys=True))] == single["metric"]

    score = cs.phase_score(single["model"], single["pred"], table, holdout,
                           parity_rows=500, auroc_floor=0.9)
    assert 0.9 < score["auroc"] <= 1.0

    serve = cs.phase_serve(single["model"], data, ROWS, ROWS + 40)
    assert serve["requests"] == 40 and serve["aotHits"] > 0
    cs.check_no_faults("end of run")


def test_main_refuses_a_backend_that_is_not_a_tpu(capsys):
    assert cs.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""            # no result line
    assert "no accelerator" in captured.err


def test_a_passing_run_ends_with_the_result_line(monkeypatch, capsys):
    """The driver reads the last stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``). The summary, ``"claim": null``
    included, is the line before it. Phases are stubbed: this drives
    main()'s own head and tail."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cs, "ROWS", 64)
    monkeypatch.setattr(cs, "HOLDOUT_ROWS", 16)
    monkeypatch.setattr(cs, "phase_native", lambda: {"textops": True})
    monkeypatch.setattr(cs, "phase_kernels", lambda seed: {"k": 0.0})
    monkeypatch.setattr(cs, "phase_train", lambda table, mesh=None: {
        "model": None, "pred": None, "family": "OpLogisticRegression",
        "hyper": {}, "metric": 0.99, "fits": cs.DEFAULT_GRID_FITS})
    monkeypatch.setattr(cs, "phase_score", lambda *a: {"auroc": 0.99})
    monkeypatch.setattr(cs, "phase_serve", lambda *a: {"requests": 1})
    monkeypatch.setattr(cs, "phase_mesh", lambda *a: {"auroc": 0.99})

    assert cs.main(["--seed", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    dev = jax.devices()[0]
    assert last["device"] == {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())}
    assert isinstance(last["device"]["count"], int)
    summary = json.loads(lines[-2])
    assert lines[-2].endswith('"claim": null}')
    assert summary["seed"] == 3 and summary["rows"] == 64
    assert "hits" in summary["compileCache"]


@pytest.mark.chaos
def test_a_quarantined_family_fails_the_fault_check():
    """train() survives a family whose program raises and elects a winner
    from the rest — exactly what must not pass for a chip run."""
    data = cs.make_data(ROWS, seed=4)   # same row bucket: programs reused
    with faults.injected({"validator.family_fit": {
            "mode": "raise", "key": "OpGBTClassifier", "count": 99}}):
        with pytest.raises(cs.SmokeFailure, match="faults.*not clean"):
            cs.phase_train(cs.table_of(data, 0, ROWS))


def test_auroc_matches_the_pairwise_definition():
    rng = np.random.RandomState(0)
    y = (rng.rand(200) > 0.4).astype(np.float32)
    s = np.round(rng.rand(200) + 0.3 * y, 1)      # rounded: many ties
    pos, neg = s[y > 0.5], s[y < 0.5]
    pairs = ((pos[:, None] > neg[None, :]).sum()
             + 0.5 * (pos[:, None] == neg[None, :]).sum())
    assert cs.auroc(s, y) == pytest.approx(pairs / (len(pos) * len(neg)))


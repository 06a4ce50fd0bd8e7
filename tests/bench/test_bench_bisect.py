"""``bench/bisect_precision.py``: its verdict and its limit rule worked by
hand, and a whole run on the CPU at a tiny size, where every variant of
the TDNN's precision table is float32 and agrees with the reference."""
from bench_fixtures import tiny_root  # noqa: F401 (a fixture; first import)

import json

import jax
import pytest

from bench import bisect_precision as bisect
from repro.models import acoustic


def _rec(what, seed, loss, grad, change, candidate=(1, 2)):
    return {"what": what, "seed": seed, "loss_gap": loss,
            "grad_norm_gap": grad, "change_gap": change,
            "candidate": list(candidate), "accepted": [True, False]}


def test_verdict_by_hand():
    recs = [_rec("all_high", 1, 4e-6, 1e-4, 1e-3),
            _rec("all_high", 2, 8e-6, 3e-4, 0.1, (1, 7)),
            # as close: each largest within twice all_high's (8e-6, 3e-4,
            # 0.1) and all_high's candidates on both seeds
            _rec("hidden_high", 1, 1.6e-5, 6e-4, 2e-3),
            _rec("hidden_high", 2, 2e-6, 1e-5, 0.2, (1, 7)),
            # the reference's candidates on seed 2, but not all_high's
            _rec("out_high", 1, 1e-6, 1e-5, 1e-4),
            _rec("out_high", 2, 1e-6, 1e-5, 1e-4, (1, 2)),
            # the same candidates, a loss gap over twice all_high's
            _rec("default", 1, 1.7e-5, 1e-4, 1e-3),
            _rec("default", 2, 1e-6, 1e-4, 1e-3, (1, 7))]
    v = bisect.verdict(recs, "all_high")
    assert v["hidden_high"]["agrees"] is True
    assert v["out_high"] == {"max": {"loss_gap": 1e-6, "grad_norm_gap": 1e-5,
                                     "change_gap": 1e-4},
                             "departs_on": [2], "agrees": False}
    assert v["default"]["departs_on"] == [] and not v["default"]["agrees"]
    assert v["default"]["max"]["loss_gap"] == 1.7e-5
    # nothing to be judged against
    assert not bisect.verdict(recs, "all_highest")["hidden_high"]["agrees"]


def test_limit_rule_by_hand():
    sound = [{"loss_gap": 4e-6, "grad_norm_gap": 1e-4, "change_gap": 0.1},
             {"loss_gap": 1e-5, "grad_norm_gap": 2e-4, "change_gap": 0.0}]
    control = [{"loss_gap": 2e-5, "grad_norm_gap": 5e-4, "change_gap": 0.2},
               {"loss_gap": 9e-5, "grad_norm_gap": 7e-4, "change_gap": 1.0},
               {"loss_gap": 4e-5, "grad_norm_gap": 1e-2, "change_gap": 0.0}]
    got = bisect.limit_rule(sound, control)
    # loss: P 1e-5, C the smallest at least 3e-5 = 4e-5 (not 2e-5),
    # sqrt(4e-10) = 2e-5, already one digit
    assert got["loss_gap"] == {"P": 1e-5, "C": 4e-5, "limit": 2e-5}
    # grad: P 2e-4, C 7e-4, sqrt(1.4e-7) = 3.74e-4, up to 4e-4
    assert got["grad_norm_gap"] == {"P": 2e-4, "C": 7e-4, "limit": 4e-4}
    # change: P 0.1, C 1.0, sqrt(0.1) = 0.316, up to 0.4
    assert got["change_gap"] == {"P": 0.1, "C": 1.0, "limit": 0.4}
    # no control reading reaches 3 P: no limit from the rule
    assert bisect.limit_rule(sound, control[:1])["loss_gap"] == {
        "P": 1e-5, "C": None, "limit": None}


def test_variants_cost_in_bf16_passes():
    table = bisect.variants()
    assert bisect.cost(table["default"]) == (1, 1)
    assert bisect.cost(table["hidden_high"]) == (3, 1)
    assert bisect.cost(table["out_highest"]) == (1, 6)
    assert bisect.cost(table["all_high"]) == (3, 3)


def test_bisect_runs_on_a_tiny_tdnn(tiny_root, capsys):
    stated = acoustic.TDNN_PRECISION
    bisect.main(["--workload", "tiny-tdnn", "--seeds", str(2**31 + 41),
                 "--variants", "default,all_high", "--timed", "1",
                 "--root", tiny_root], devices=jax.devices()[:1])
    assert acoustic.TDNN_PRECISION is stated
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    recs = [r for r in lines if "what" in r]
    assert [r["what"] for r in recs] == ["default", "all_high"]
    for r in recs:
        # f32 on the CPU: the same candidates, gaps at rounding
        assert r["candidate"] == r["ref_candidate"]
        assert r["loss_gap"] < 1e-5 and r["grad_norm_gap"] < 1e-5
        assert r["update_ms"] > 0
    verdicts = {r["variant"]: r for r in lines if "variant" in r}
    assert verdicts["default"]["judged"] is True
    assert verdicts["all_high"]["judged"] is False
    assert verdicts["default"]["agrees"] is True
    assert "limit_rule" in lines[-1]


def test_a_variant_that_fails_is_reported_and_the_rest_run(tiny_root,
                                                          capsys,
                                                          monkeypatch):
    from bench import trainer

    real = trainer.Trainer

    def refuse_hidden_high(cell, seed, **kw):
        if acoustic.TDNN_PRECISION["output"] is None \
                and acoustic.TDNN_PRECISION["hidden"] is not None:
            raise RuntimeError("scoped VMEM exceeded")
        return real(cell, seed, **kw)

    monkeypatch.setattr(trainer, "Trainer", refuse_hidden_high)
    stated = acoustic.TDNN_PRECISION
    seeds = f"{2**31 + 43},{2**31 + 44}"
    bisect.main(["--workload", "tiny-tdnn", "--seeds", seeds,
                 "--variants", "hidden_high,default", "--timed", "0",
                 "--root", tiny_root], devices=jax.devices()[:1])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    # tried once, then left out; the other variant runs on both seeds
    assert [ln["error"] for ln in lines if "error" in ln] == [
        "scoped VMEM exceeded"]
    assert [r["what"] for r in lines if "what" in r] == ["default"] * 2
    assert [r["variant"] for r in lines if "judged" in r] == ["default"]
    assert acoustic.TDNN_PRECISION is stated


def test_unknown_variant_is_refused(tiny_root):
    with pytest.raises(SystemExit):
        bisect.main(["--workload", "tiny-tdnn", "--seeds", "1",
                     "--variants", "all_bf16", "--root", tiny_root],
                    devices=jax.devices()[:1])

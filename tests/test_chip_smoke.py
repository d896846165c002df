"""``chip_smoke.py``'s phases at a tiny width on the CPU backend.

The script itself refuses to run off a TPU; these tests drive the same phase
functions on a 2-layer model with 64-word lanes, so the serving flow, the
flip/oracle/upgrade checks, the dense agreement, the object cache check and
the 4-bank sharded comparison are exercised on every test run.
"""
import jax
import pytest

import chip_smoke
from repro.configs.base import ModelConfig

CFG = ModelConfig(name="smoke-tiny", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=256, head_dim=16, dtype="float32")
# 512-word pages hold 8 tokens of K+V, as qwen3-0.6b's 64 KiB pages do;
# three batch sessions outgrow the 16-row CREAM region into its extras
TINY = chip_smoke.Geometry(row_words=64, num_rows=64, cream_rows=16,
                           upgrade_to=8, max_batch=4, max_len=64,
                           prompt_len=12, max_new=4, sessions=4, turns=2,
                           paid=1, obj_values=256, obj_words=16)


def test_cream_run_checks_flip_oracle_upgrade_and_dense():
    checks = {}
    eng, prompts, reqs, run = chip_smoke.serve_cream(CFG, TINY, 0, checks)
    assert checks["oracle_pages"] > 1
    assert checks["upgrade"]["migrated"] > 0
    assert checks["regions_at_first_step"]["secded"] > 0
    served = chip_smoke.tokens_by_session(reqs)
    assert all(len(t) == TINY.turns * TINY.max_new for t in served.values())
    agree = chip_smoke.dense_agreement(eng, prompts, served)
    assert agree["tokens"] == TINY.sessions * TINY.turns * TINY.max_new


def test_upgrade_without_mapped_extras_fails_the_smoke():
    small = chip_smoke.Geometry(**{**TINY.__dict__, "sessions": 2})
    with pytest.raises(chip_smoke.SmokeFailure, match="never mapped"):
        chip_smoke.serve_cream(CFG, small, 0, {})


def test_objcache_phase_matches_dict():
    out = chip_smoke.objcache_phase(TINY, 0)
    assert out["hits"] == TINY.obj_values
    assert out["gets"] == TINY.obj_values + 64


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_four_chip_phase_on_virtual_devices():
    tiny4 = chip_smoke.Geometry(**{**TINY.__dict__, "cream_rows": 32})
    chip_smoke.four_chips(CFG, tiny4, 0)


def test_refuses_to_run_off_tpu(capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 2
    assert capsys.readouterr().out == ""

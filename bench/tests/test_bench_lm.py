"""The LM cell's driver on the CPU at the SMOKE widths of
``repro.configs.granite_4h_small``: a sound run is correct, the control (the
reference with its Mamba2 state in bfloat16) fails a limit, and the per-layer
readers read the run's record."""

import copy
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import lm_work  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CELL = "granite_h_chat_p80"
SEED = 2**33 + 777
SECONDS = 0.5
#: SMOKE widths, both layer kinds, 2 of 8 experts held, top-2
SMOKE = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 32, "shared_intermediate_size": 48, "mamba_d_state": 16,
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_chunk_size": 8,
         "num_hidden_layers": 4, "num_local_experts": 2, "expert_offset": 2,
         "num_experts_per_tok": 2, "vocab_size": 256, "attention_multiplier": 0.0625,
         "layer_types": ["mamba", "attention", "mamba", "mamba"]}
#: limits for these widths (the cell's own hold at its published widths)
LIMITS = {"logits_rel_gap_p50": 0.05, "logits_rel_gap_max": 0.2,
          "ssm_state_rel_gap": 0.01, "unanswered": 0.0}


def small_cell():
    cell = harness.resolve(CELL)
    cell.config = dict(copy.deepcopy(cell.config), **SMOKE)
    cell.config["published"] = dict(cell.config["published"], num_local_experts=8)
    cell.config["topology"] = SMOKE["layer_types"]
    cell.config["engine"] = dict(cell.config["engine"], slots=4, max_len=64,
                                 min_bucket=8, max_bucket=32)
    cell.config["limits"] = LIMITS
    cell.traffic = dict(cell.traffic, rate_hz=10.0,
                        prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 32},
                        output={"median": 6, "sigma": 0.7, "min": 3, "max": 16})
    return cell


@pytest.fixture
def v5e_peaks(monkeypatch):
    real = work.peaks
    monkeypatch.setattr(work, "peaks", lambda kind, path=work.PEAKS_FILE:
                        real("TPU v5 lite", path))


def _driver():
    cell = small_cell()
    return harness.load_module(cell.driver_path, "bench_driver_lm").Driver, cell


def test_sound_run_is_correct_and_the_readers_read_it(v5e_peaks):
    import jax

    drv_cls, cell = _driver()
    out = run.run_cell(cell, SEED, SECONDS, False, jax.devices()[:1],
                       t_start=time.perf_counter(), driver_factory=drv_cls)
    assert out["correct"], [(c.name, c.value, c.limit) for c in out["checks"]]
    assert out["failed"] == 0 and out["attempted"] == 5
    assert set(out["metrics"]) == {"latency_p90_ms", "setup_s"}


def test_control_fails_and_records_feed_every_reader(v5e_peaks):
    drv_cls, cell = _driver()
    drv = drv_cls(cell, SEED, SECONDS, False)
    drv.setup()
    res = drv.window()
    drv.release()
    assert all(c.ok for c in drv.checks())
    assert [c.name for c in drv.control() if not c.ok]
    rec = dict(res["record"], window_s=res["window_s"], chips=1, config=cell.config,
               peaks=work.peaks("TPU v5 lite"),
               trace={"busy_s": res["window_s"], "window_s": res["window_s"]})
    lm = rec["lm"]
    assert lm["requests"] == 5 and lm["pulls"] == lm["decode_steps"] > 0
    assert lm["decode_tokens"] + 5 == sum(drv.olen)
    assert lm["prefill_lengths"] and len(lm["queue_wait_s"]) == 5
    got = harness.read_per_layer(cell, rec)
    assert set(got) == {"lm.queue_wait_p90_ms", "lm.host_ms_per_step", "lm.pad_share",
                        "lm_step_roofline", "mfu.lm", "device.idle_share.serve",
                        "client.wake_lag_p99_ms"}
    assert 0 < got["lm.pad_share"]["value"] < 100
    assert got["lm_step_roofline"]["bound"] in ("compute", "memory")
    assert all(v["value"] >= 0 for v in got.values())


def test_work_counts_at_published_widths():
    """The cut that the configuration file states: 8.12 GB of held weights,
    2.42 GB of SSM state and 0.03 GB of conv state at 32 slots, 0.67 GB of
    KV, and 5.30 GFLOP a token (2 x 2.65 G MACs: Mamba2 mixers 18 x 102.3 M,
    attention 2 x 41.9 M, 1.25 held experts of 9.4 M, the shared expert of
    18.9 M and the router in each layer, the SSM recurrence 18 x 3.1 M, the
    logits 51.4 M)."""
    cfg = harness.resolve(CELL).config
    s = lm_work.shapes(cfg)
    assert (s.n_mamba, s.n_attn, s.held, s.n_experts) == (18, 2, 9, 72)
    assert lm_work.weight_bytes(s) / 1e9 == pytest.approx(8.12, abs=0.01)
    assert 32 * lm_work.state_bytes(s) / 1e9 == pytest.approx(2.445, abs=0.001)
    assert 2 * lm_work.macs_per_token(s) / 1e9 == pytest.approx(5.304, abs=0.001)
    assert 32 * 2560 * lm_work.kv_bytes_per_position(s) / 1e9 == pytest.approx(0.67, abs=0.01)
    flops, nbytes = lm_work.decode_work(s, 1, 32, 32 * 1000)
    t, bound = lm_work.least_time(flops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.015 < t < 0.02


def test_cell_config_keeps_every_published_number():
    """Every number of the published config is in the file under its key;
    only the keys listed in ``reduced`` differ, each beside its published
    value."""
    cfg = harness.resolve(CELL).config
    from repro.configs import granite_4h_small as g

    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["topology"] == list(g.LAYER_TYPES[: cfg["num_hidden_layers"]])
    assert cfg["layer_types"] == list(g.LAYER_TYPES)
    drv = harness.load_module(harness.resolve(CELL).driver_path, "bench_driver_lm2")
    mc = drv.model_config(cfg)
    full = g.CONFIG
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "top_k",
                  "shared_expert_ff", "ssm_state", "ssm_heads", "ssm_head_dim",
                  "ssm_groups", "ssm_expand", "ssm_chunk", "layer_types",
                  "position_embedding", "embedding_multiplier", "residual_multiplier",
                  "attention_multiplier", "logits_scaling", "norm_eps",
                  "tied_embeddings", "n_experts"):
        assert getattr(mc, field) == getattr(full, field), field
    assert (mc.n_layers, mc.experts_held, mc.vocab_size) == (20, 9, 12544)
    assert np.isclose(full.vocab_size / mc.vocab_size, 8)

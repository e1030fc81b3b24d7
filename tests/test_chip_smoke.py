"""CPU rehearsal of the chip proof (ISSUE 23): chip_smoke.py's phases at
20k rows through the function the script exposes, and bench.py's exit
code when a rung raises.  The two backend assertions of the script are
stubbed HERE — the script itself has no option that waives them."""
import json
import signal

import pytest


def test_chip_smoke_phases_rehearsal_on_cpu(monkeypatch, capsys):
    import chip_smoke

    # under the CPU backend the Pallas kernel runs interpreted; the
    # compiled-kernel proof is tests/test_tpu_compile.py's and the chip's
    monkeypatch.setattr(chip_smoke, "require_compiled_kernel",
                        lambda text: None)
    chip_smoke.run_single_chip(20_000, 20_000)
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith("{")]
    phases = {ln["phase"]: ln for ln in lines}
    assert list(phases) == ["setup", "q6_parquet", "q6_hot", "qa_join_agg",
                            "qb_left_join", "qc_window", "decode"]
    for name, rec in phases.items():
        if name == "setup":
            continue
        assert rec["rows"] == 20_000 and rec["nProgramsLaunched"] > 0
        assert all(rec[k] == 0 for k in chip_smoke.FALLBACK_COUNTERS)
    # the phase named "shuffled left join" ran one: both sides through an
    # exchange, and the adaptive join kept the shuffled plan at run time
    assert phases["qb_left_join"]["joinDecision"].startswith("shuffled")
    assert "--- qb_left_join executed plan ---" in out
    assert "TpuShuffleExchange" in out.split(
        "--- qb_left_join executed plan ---")[1].split("\n{")[0]
    assert phases["decode"]["deviceDecode_s"] > 0
    assert phases["decode"]["unpackPrograms"]


def test_chip_smoke_refuses_a_plan_of_another_shape():
    """A phase whose executed plan is not the shape it names fails: qb
    planned with the default broadcast threshold is a broadcast join."""
    import bench
    import chip_smoke
    from spark_rapids_tpu.exec.exchange import (
        TpuBroadcastExchangeExec,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.session import TpuSession

    ss = bench.make_store_sales(2_000)
    sr = bench.make_store_returns(ss, 200)
    root = bench.build_qb(TpuSession(dict(chip_smoke.BASE_CONF)),
                          ss, sr)._planned()[0]
    chip_smoke._assert_plan_shape("qb", root, (TpuBroadcastExchangeExec,),
                                  (TpuShuffleExchangeExec,))
    with pytest.raises(AssertionError, match="no TpuShuffleExchangeExec"):
        chip_smoke._assert_plan_shape("qb", root,
                                      (TpuShuffleExchangeExec,), ())
    with pytest.raises(AssertionError, match="TpuBroadcastExchangeExec in"):
        chip_smoke._assert_plan_shape("qb", root, (),
                                      (TpuBroadcastExchangeExec,))


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    import chip_smoke

    assert chip_smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": False, "device": None}


def test_chip_smoke_detects_an_interpreted_kernel():
    import chip_smoke

    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.require_compiled_kernel("module @jit_f { stablehlo.add }")
    chip_smoke.require_compiled_kernel(
        'stablehlo.custom_call @tpu_custom_call')


def test_bench_exits_nonzero_when_a_rung_raises(monkeypatch, capsys):
    """A rung that raises used to be logged and forgotten (rc 0); now the
    record keeps the other rungs, names the failure, and the exit code
    says so."""
    import bench
    from spark_rapids_tpu.memory import spill

    for knob, val in {
            "BENCH_ROWS": "2000", "BENCH_REPEATS": "1", "BENCH_OUT": "0",
            "BENCH_DIAG_DIR": "0", "BENCH_PROFILE_DIR": "0",
            "BENCH_RUNG3_OOC": "0", "BENCH_RUNG4_DIST": "0",
            "BENCH_RUNG5_RECOVERY": "0", "BENCH_PARQUET": "0",
            "BENCH_PROGRESS_OVERHEAD": "0",
            "BENCH_ACCOUNTING_OVERHEAD": "0"}.items():
        monkeypatch.setenv(knob, val)

    def boom():
        raise RuntimeError("rung3 broke")

    monkeypatch.setattr(spill, "reset_spill_framework", boom)
    handlers = {s: signal.getsignal(s)
                for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        rc = bench.main()
    finally:
        for s, h in handlers.items():
            signal.signal(s, h)
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0
    assert any("rung3" in f and "rung3 broke" in f
               for f in payload["failed"])
    # the rungs before it are all in the record, on a named device
    assert {"q6_hot", "qa_join_agg_hot", "qb_left_join_hot",
            "qc_window_hot"} <= set(payload["queries"])
    assert payload["platform"] == "cpu" and payload["device_count"] >= 1
    assert "hbm_frac" not in payload["queries"]["q6_hot"]
    for q in payload["queries"].values():
        for k in ("nRuntimeFallbacks", "nQueryFallbacks",
                  "nBreakerPlanFallbacks", "nAdvisorPlanFallbacks",
                  "nFileDecoderFallbacks", "nChunkDecodeFallbacks"):
            assert q[k] == 0

"""launch/serve.py CLI: legacy flag spellings map onto EngineConfig with
DeprecationWarnings, and the canonical --config/--set surface is equivalent."""
import warnings

import pytest

from repro.engine import EngineConfig
from repro.launch.serve import build_parser, config_from_args


def _resolve(argv):
    args = build_parser().parse_args(argv)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = config_from_args(args)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    return cfg, dep


# every legacy spelling next to its canonical --set equivalent; the two must
# resolve to the SAME EngineConfig (legacy additionally warns)
LEGACY_CASES = [
    (["--planner", "symmetric"], ["--set", "planner=symmetric"], 1),
    (["--planner", "asymmetric"], [], 1),  # the default, spelled explicitly
    (["--layout", "dense"], ["--set", "layout=dense"], 1),
    (["--kernels", "xla"], ["--set", "use_kernels=xla"], 1),
    (["--reduce", "psum"], ["--set", "reduce_mode=psum"], 1),
    (["--reduce", "ring"], ["--set", "reduce_mode=ring"], 1),
    (["--autotune"], ["--set", "tuning=sweep"], 1),
    (["--dedup"], ["--set", "access=dedup"], 1),
    (["--cache"], ["--set", "access=cache"], 1),
    (["--dedup", "--cache"], ["--set", "access=full"], 2),
    (["--replan"], ["--set", "drift=replan"], 1),
    (
        ["--replan", "--replan-threshold", "0.3"],
        ["--set", "drift=replan",
         "--set", 'drift_options={"threshold": 0.3}'],
        2,
    ),
    # threshold alone is recorded but does NOT arm replanning (the old
    # CLI ignored it without --replan)
    (
        ["--replan-threshold", "0.3"],
        ["--set", 'drift_options={"threshold": 0.3}'],
        1,
    ),
]


@pytest.mark.parametrize(
    "legacy,canonical,n_warnings",
    LEGACY_CASES,
    ids=[" ".join(c[0]) for c in LEGACY_CASES],
)
def test_legacy_flag_equivalent_config(legacy, canonical, n_warnings):
    legacy_cfg, dep = _resolve(legacy)
    assert len(dep) == n_warnings
    for w in dep:
        assert "deprecated" in str(w.message)
        assert "EngineConfig" in str(w.message)
    canonical_cfg, dep_canon = _resolve(canonical)
    assert not dep_canon, "the canonical spelling must not warn"
    assert legacy_cfg == canonical_cfg


def test_defaults_do_not_warn():
    cfg, dep = _resolve([])
    assert not dep
    assert cfg.planner == "asymmetric"
    # the serve CLI's historical choices are baked into the resolved config
    assert cfg.planner_options == {"shard_rocks": True}
    assert cfg.distribution == "real"  # traffic default doubles as pricing
    assert cfg.drift == "none"


def test_replan_gets_cli_trigger_cadence():
    cfg, _ = _resolve(["--replan"])
    assert cfg.drift == "replan"
    assert cfg.drift_options == {
        "check_every": 4, "patience": 2, "cooldown": 8,
    }


def test_distribution_all_prices_uniform_leg():
    cfg, _ = _resolve(["--distribution", "all"])
    assert cfg.distribution == "uniform"


def test_batch_flags_flow_into_serving_config():
    cfg, _ = _resolve(["--batch", "64"])
    assert cfg.max_batch == 64 and cfg.max_wait_s == 0.0


def test_replan_threshold_alone_stays_static():
    cfg, dep = _resolve(["--replan-threshold", "0.3"])
    assert len(dep) == 1
    assert cfg.drift == "none"
    assert cfg.drift_options == {"threshold": 0.3}


def test_set_and_config_serving_knobs_not_clobbered(tmp_path):
    # --set wins over --batch; a --config file's serving knobs survive
    cfg, _ = _resolve(["--batch", "64", "--set", "max_batch=512"])
    assert cfg.max_batch == 512
    base = EngineConfig(max_batch=128, max_wait_s=0.002)
    path = tmp_path / "engine.json"
    base.save(path)
    cfg2, _ = _resolve(["--config", str(path)])
    assert cfg2.max_batch == 128 and cfg2.max_wait_s == 0.002
    cfg3, _ = _resolve(["--config", str(path), "--batch", "64"])
    assert cfg3.max_batch == 64  # explicit --batch overrides the file


def test_config_file_roundtrip(tmp_path):
    base = EngineConfig(distribution="zipf:1.4", access="full",
                        tuning="sweep")
    path = tmp_path / "engine.json"
    base.save(path)
    cfg, dep = _resolve(["--config", str(path)])
    assert not dep
    assert cfg.access == "full" and cfg.tuning == "sweep"
    assert cfg.distribution == "zipf:1.4"  # config pins pricing over traffic
    # legacy flags still override a loaded config (with the warning)
    cfg2, dep2 = _resolve(["--config", str(path), "--reduce", "psum"])
    assert len(dep2) == 1 and cfg2.reduce_mode == "psum"


def test_set_rejects_unknown_field():
    args = build_parser().parse_args(["--set", "bogus=1"])
    with pytest.raises(SystemExit):
        config_from_args(args)


# ------------------------------------------------------------ preset packs


def test_list_presets_names_the_curated_packs():
    from repro.configs.presets import list_presets

    names = list_presets()
    assert {"taobao-zipf12", "tenrec-hotset", "huawei-dayparted"} <= set(names)


@pytest.mark.parametrize("name", [
    "taobao-zipf12", "tenrec-hotset", "huawei-dayparted",
])
def test_load_preset_validates_and_roundtrips(name):
    from repro.configs.presets import load_preset

    data = load_preset(name)
    assert data["name"] == name
    assert data["description"]
    # the embedded config is a valid EngineConfig (load_preset validates,
    # but the round-trip must also be loss-free)
    cfg = EngineConfig.from_dict(data["config"])
    assert cfg.to_dict() | data["config"] == cfg.to_dict()


def test_load_preset_unknown_name_lists_alternatives():
    from repro.configs.presets import load_preset

    with pytest.raises(ValueError, match="taobao-zipf12"):
        load_preset("nope")


def test_preset_fills_config_workload_and_distribution():
    cfg, dep = _resolve(["--preset", "tenrec-hotset"])
    assert not dep
    assert cfg.validation == "null-row" and cfg.integrity == "checksum"
    assert cfg.access == "full" and cfg.admission == "shed-oldest"
    # the preset also resolved the driver flags on the namespace
    args = build_parser().parse_args(["--preset", "tenrec-hotset"])
    config_from_args(args)
    assert args.workload == "tenrec-qb"
    assert args.distribution == "tenrec-qb"


def test_explicit_flags_override_preset():
    args = build_parser().parse_args(
        ["--preset", "taobao-zipf12", "--workload", "smoke",
         "--distribution", "uniform", "--set", "max_batch=64"]
    )
    cfg = config_from_args(args)
    assert args.workload == "smoke" and args.distribution == "uniform"
    assert cfg.max_batch == 64
    assert cfg.drift == "replan"  # the rest of the pack survives


def test_preset_and_config_are_mutually_exclusive(tmp_path):
    path = tmp_path / "engine.json"
    EngineConfig().save(path)
    args = build_parser().parse_args(
        ["--preset", "taobao-zipf12", "--config", str(path)]
    )
    with pytest.raises(SystemExit, match="mutually exclusive"):
        config_from_args(args)


def test_structural_validation_still_enforced():
    # the old `p.error("--dedup/--cache require ...")` checks now live in
    # EngineConfig.validate
    args = build_parser().parse_args(["--dedup", "--planner", "baseline"])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(ValueError, match="planner='asymmetric'"):
            config_from_args(args)
    args = build_parser().parse_args(["--cache", "--kernels", "xla"])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(ValueError, match="use_kernels='fused'"):
            config_from_args(args)


# ------------------------------------------------------------ exit status


def _crash_forward(monkeypatch, kernels_only):
    """Make the DLRM forward raise — on every executor, or only on the fused
    kernel path (the XLA reference fallback then serves degraded)."""
    import repro.launch.serve as serve
    import repro.models.dlrm as dlrm
    from repro import compat

    real = dlrm.forward_packed

    def forward(*args, use_kernels="fused", **kw):
        if use_kernels or not kernels_only:
            raise RuntimeError("injected step crash")
        return real(*args, use_kernels=use_kernels, **kw)

    monkeypatch.setattr(dlrm, "forward_packed", forward)
    monkeypatch.setattr(compat, "enable_compilation_cache", lambda: "")
    return serve


_SMOKE_ARGV = ["--workload", "smoke", "--batch", "16", "--queries", "48",
               "--distribution", "uniform"]


@pytest.mark.parametrize("kernels_only", [False, True],
                         ids=["every-path", "fused-only-degraded"])
def test_step_crash_exits_nonzero(monkeypatch, capsys, kernels_only):
    serve = _crash_forward(monkeypatch, kernels_only)
    with pytest.raises(SystemExit) as exc:
        serve.main(_SMOKE_ARGV)
    assert exc.value.code == 1
    out = capsys.readouterr().out
    key = "degraded_batches" if kernels_only else "batch_failures"
    assert "[serve] FAILED:" in out and key in out


def test_clean_run_exits_zero(monkeypatch, capsys):
    from repro import compat
    from repro.launch.serve import main

    monkeypatch.setattr(compat, "enable_compilation_cache", lambda: "")
    main(_SMOKE_ARGV)  # returns: nothing failed, nothing degraded
    assert "FAILED" not in capsys.readouterr().out

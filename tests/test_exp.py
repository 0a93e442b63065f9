"""Declarative experiment layer: grammar, specs, artifacts, resume."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

import repro.eval.sweep as sweep_mod
from repro.core.critic import Critic, init_params
from repro.eval import cli
from repro.exp import (ArtifactError, ExperimentSpec, FingerprintMismatch,
                       GrammarError, SpecError, format_method,
                       format_scenario, format_value, parse_method,
                       parse_methods, parse_scenario, parse_seeds,
                       parse_value, resolve_artifact, run_experiment,
                       save_critic)
from repro.exp.provenance import completed_rows

MOCK_LLM = pathlib.Path(__file__).resolve().parent / "mock_llm.py"


# --------------------------------------------------------------------------- #
# grammar
# --------------------------------------------------------------------------- #
def test_value_round_trip():
    for v in (3, -1, 0.75, 1.0, 2.5e-3, True, False, None, "qwen3-32b-sim",
              "@critic?", "a b, (c)=d", 'quo"te', "back\\slash", "0.75",
              "none", "rho=0.75", ""):
        assert parse_value(format_value(v)) == v, v


def test_parse_method_forms():
    assert parse_method("haf-static") == \
        {"name": "haf-static", "params": {}, "label": "haf-static"}
    m = parse_method("haf(agent=qwen3-32b-sim, critic=@critic, K=3)")
    assert m["name"] == "haf"
    assert m["params"] == {"agent": "qwen3-32b-sim",
                           "critic_path": "@critic", "K": 3}
    m = parse_method('caora(alpha=0.4, label=CAORA)')
    assert m == {"name": "caora", "params": {"alpha": 0.4}, "label": "CAORA"}


def test_haf_llm_cmd_may_contain_commas():
    cmd = 'curl -s localhost:8000 -d {"a": 1, "b": [2, 3]} | jq .text'
    m = parse_method(f'haf-llm(cmd="{cmd.replace(chr(92), "")}")')
    assert m["params"]["cmd"] == cmd.replace(chr(92), "")
    assert parse_method(format_method(m)) == m


def test_legacy_haf_llm_sugar_still_parses():
    m = parse_method("haf-llm:curl -s localhost")
    assert m["name"] == "haf-llm"
    assert m["params"] == {"cmd": "curl -s localhost"}
    assert m["label"] == "haf-llm(curl -s localhost)"


def test_legacy_haf_llm_with_comma_errors_at_parse():
    # the legacy sugar next to a comma is ambiguous (command comma vs
    # method separator; the old parser silently truncated the command) —
    # it must error with a pointer at the grammar form, even when the
    # post-comma fragment happens to be a valid method name
    for text in ("haf-llm:curl -s x --data a, b",
                 "haf-llm:python serve.py --modes a,haf",
                 "haf-static,haf-llm:curl -s x"):
        with pytest.raises(GrammarError, match=r'haf-llm\(cmd='):
            parse_methods(text)
    # alone (no commas) the legacy sugar still works…
    assert parse_methods("haf-llm:curl -s x")[0]["params"]["cmd"] \
        == "curl -s x"
    # …and a spec-file list entry is never comma-split, so a legacy entry
    # there keeps its full command
    spec = ExperimentSpec(methods=("haf-llm:curl -s x --data a,b",),
                          scenarios=("paper",))
    assert spec.methods[0]["params"]["cmd"] == "curl -s x --data a,b"


def test_method_grammar_round_trip():
    for text in ("haf-static",
                 "haf(K=5, agent=qwen2.5-72b-sim, critic_path=@critic?)",
                 'haf-llm(cmd="vllm serve m, n --port 80", timeout=9.5)',
                 "caora(alpha=0.25, label=CAORA)",
                 "lyapunov(V=0.5)"):
        m = parse_method(text)
        assert parse_method(format_method(m)) == m, text


def test_scenario_grammar_round_trip():
    for text in ("paper",
                 "flash-crowd(magnitude=6.0, n_spikes=2, rho=0.95)",
                 'paper(n_ai_requests=3750, rho=0.75, label="rho=0.75")'):
        s = parse_scenario(text)
        assert parse_scenario(format_scenario(s)) == s, text


def test_parse_seeds_forms():
    assert parse_seeds("3") == [0, 1, 2]
    assert parse_seeds("0,2,5") == [0, 2, 5]
    assert parse_seeds("0..4") == [0, 1, 2, 3, 4]
    assert parse_seeds("0,") == [0]
    assert parse_seeds("0..1,7") == [0, 1, 7]


def test_parse_seeds_zero_points_at_spec_form():
    with pytest.raises(GrammarError, match="seeds = \\[0\\]"):
        parse_seeds("0")
    with pytest.raises(GrammarError):
        parse_seeds("-2")
    with pytest.raises(GrammarError):
        parse_seeds("1..x")


# --------------------------------------------------------------------------- #
# ExperimentSpec
# --------------------------------------------------------------------------- #
MINI_KW = dict(methods=("haf-static", "round-robin"),
               scenarios=("paper", "skewed-hetero(n_nodes=4)"),
               seeds=(0, 1), n_ai_requests=120)


def test_spec_file_round_trip(tmp_path):
    spec = ExperimentSpec(name="mini", workers=2, **MINI_KW)
    for suffix in (".toml", ".json"):
        path = spec.to_file(tmp_path / f"mini{suffix}")
        back = ExperimentSpec.from_file(path)
        assert back.spec_hash() == spec.spec_hash(), suffix
        assert back.expand() == spec.expand(), suffix


def test_spec_grammar_equals_raw_dicts():
    by_grammar = ExperimentSpec(
        methods=("haf(agent=qwen3-32b-sim, critic=@c?)",
                 "caora(alpha=0.3)"),
        scenarios=("flash-crowd(rho=0.95, n_ai_requests=400)",))
    by_dicts = ExperimentSpec(
        methods=({"name": "haf",
                  "params": {"agent": "qwen3-32b-sim",
                             "critic_path": "@c?"}, "label": "haf"},
                 {"name": "caora", "params": {"alpha": 0.3},
                  "label": "caora"}),
        scenarios=({"family": "flash-crowd",
                    "params": {"rho": 0.95, "n_ai_requests": 400},
                    "label": "flash-crowd"},))
    assert by_grammar.expand() == by_dicts.expand()
    assert by_grammar.identity_hash() == by_dicts.identity_hash()


def test_spec_expand_matches_sweep(tmp_path):
    from repro.eval import expand_jobs
    spec = ExperimentSpec(**MINI_KW)
    assert spec.expand() == expand_jobs(spec.to_sweep_spec())
    assert len(spec.expand()) == 2 * 2 * 2


def test_identity_hash_scope():
    spec = ExperimentSpec(**MINI_KW)
    # non-result-affecting knobs keep the identity (resume survives them)
    assert spec.replace(workers=8, engine="scalar", batch=4, seeds=(0,),
                        name="x", out="y.json").identity_hash() \
        == spec.identity_hash()
    # result-affecting knobs change it
    assert spec.replace(n_ai_requests=121).identity_hash() \
        != spec.identity_hash()
    assert spec.with_scenario_params("paper", rho=0.8).identity_hash() \
        != spec.identity_hash()


def test_with_params_selectors():
    spec = ExperimentSpec(
        methods=("caora(alpha=0.5, label=CAORA)", "haf-static"),
        scenarios=("paper",))
    out = spec.with_method_params("CAORA", alpha=0.125)
    assert out.methods[0]["params"]["alpha"] == 0.125
    with pytest.raises(SpecError, match="no method matches"):
        spec.with_method_params("nope", alpha=1.0)


def test_validate_catches_everything():
    cases = [
        (dict(methods=("definitely-not-a-method",)), "unknown method"),
        (dict(scenarios=("not-a-family",)), "unknown scenario family"),
        (dict(scenarios=("flash-crowd(magnitud=6)",)), "unknown parameter"),
        (dict(methods=("haf(agnt=x)",)), "unknown parameter"),
        (dict(methods=("haf-llm",)), "needs cmd="),
        (dict(engine="pallas"), "batch > 1"),
        (dict(seeds=()), "no seeds"),
        # duplicate labels would merge aggregation cells and cross-resume
        (dict(scenarios=("paper(rho=0.75)", "paper(rho=1.25)")),
         "duplicate scenario labels"),
        (dict(methods=("haf(K=3)", "haf(K=5)")), "duplicate method labels"),
    ]
    for kw, match in cases:
        spec = ExperimentSpec(**{**dict(methods=("haf-static",),
                                        scenarios=("paper",)), **kw})
        with pytest.raises(SpecError, match=match):
            spec.validate()


def test_spec_file_unknown_key(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"methods": ["haf-static"], "typo_key": 1}))
    with pytest.raises(SpecError, match="typo_key"):
        ExperimentSpec.from_file(path)


# --------------------------------------------------------------------------- #
# artifact store
# --------------------------------------------------------------------------- #
def _tiny_critic(seed: int = 0) -> Critic:
    return Critic(params=init_params(jax.random.PRNGKey(seed), hidden=8))


def test_artifact_refs(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_ARTIFACTS", str(tmp_path))
    critic = _tiny_critic()
    save_critic(critic, tmp_path / "critic.json", families=("paper",),
                data_hash="d" * 64)
    path, fp = resolve_artifact("@critic")
    assert pathlib.Path(path) == tmp_path / "critic.json"
    assert fp == critic.fingerprint()
    # optional refs: absent -> (None, None), never an error
    assert resolve_artifact("@nope?") == (None, None)
    with pytest.raises(ArtifactError, match="@nope"):
        resolve_artifact("@nope")
    # fingerprint pins
    pin = f"critic@{critic.fingerprint()[:10]}"
    assert resolve_artifact(pin) == (path, critic.fingerprint())
    with pytest.raises(ArtifactError, match="no artifact"):
        resolve_artifact("critic@" + "0" * 12)
    # plain paths resolve to themselves and pick up the sidecar manifest
    ppath, pfp = resolve_artifact(str(tmp_path / "critic.json"))
    assert (ppath, pfp) == (str(tmp_path / "critic.json"),
                            critic.fingerprint())


def test_load_critic_verifies_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_ARTIFACTS", str(tmp_path))
    from repro.eval.policies import _load_critic
    critic = _tiny_critic()
    save_critic(critic, tmp_path / "critic.json", families=("paper",))
    loaded = _load_critic("@critic")
    assert loaded.fingerprint() == critic.fingerprint()
    # artifact changes under a stale manifest -> load must raise
    _tiny_critic(seed=1).save(str(tmp_path / "critic.json"))
    with pytest.raises(FingerprintMismatch):
        _load_critic("@critic")
    # a plain path with no manifest stays unverified (legacy behavior)
    _tiny_critic(seed=2).save(str(tmp_path / "bare.json"))
    assert _load_critic(str(tmp_path / "bare.json")) is not None
    # optional ref without artifact -> agent-only (None)
    assert _load_critic("@absent?") is None


# --------------------------------------------------------------------------- #
# provenance + resume
# --------------------------------------------------------------------------- #
@pytest.fixture()
def small_spec(tmp_path):
    return ExperimentSpec(methods=("haf-static",), scenarios=("paper",),
                          seeds=(0, 1), n_ai_requests=100,
                          out=str(tmp_path / "report.json"))


def _row_key(r):
    return (r["method"], r["scenario"], r["seed"])


def test_report_embeds_provenance(small_spec):
    report = run_experiment(small_spec, resume=False)
    prov = report["provenance"]
    assert prov["spec_hash"] == small_spec.spec_hash()
    assert prov["identity_hash"] == small_spec.identity_hash()
    assert prov["spec"]["methods"][0]["name"] == "haf-static"
    assert len(prov["scenario_fingerprints"]["paper"]) == 64
    assert prov["backend"]["engine"] == "numpy"
    # report round-trips as strict JSON with provenance intact
    loaded = json.loads(pathlib.Path(small_spec.out).read_text())
    assert loaded["provenance"]["spec_hash"] == small_spec.spec_hash()


def test_resume_skips_completed_rows(small_spec, monkeypatch):
    ran = []
    real = sweep_mod.run_sweep

    def counting(spec, verbose=False, jobs=None):
        ran.append(0 if jobs is None else len(jobs))
        return real(spec, verbose=verbose, jobs=jobs)

    monkeypatch.setattr(sweep_mod, "run_sweep", counting)
    r1 = run_experiment(small_spec)
    assert ran == [2] and r1["provenance"]["resumed_rows"] == 0

    # identical rerun: everything resumes, nothing runs
    r2 = run_experiment(small_spec)
    assert ran == [2] and r2["provenance"]["resumed_rows"] == 2
    assert sorted(map(_row_key, r2["runs"])) \
        == sorted(map(_row_key, r1["runs"]))

    # partial report: drop one row -> exactly one job recomputes
    path = pathlib.Path(small_spec.out)
    report = json.loads(path.read_text())
    report["runs"] = report["runs"][:1]
    path.write_text(json.dumps(report))
    r3 = run_experiment(small_spec)
    assert ran == [2, 1] and r3["provenance"]["resumed_rows"] == 1
    for a, b in zip(sorted(r1["runs"], key=_row_key),
                    sorted(r3["runs"], key=_row_key)):
        assert a["overall"] == b["overall"]
        assert a["n_events"] == b["n_events"]

    # resume=False recomputes everything
    r4 = run_experiment(small_spec, resume=False)
    assert ran == [2, 1, 2] and r4["provenance"]["resumed_rows"] == 0

    # a result-affecting change invalidates the prior rows
    r5 = run_experiment(small_spec.replace(n_ai_requests=101,
                                           out=small_spec.out))
    assert ran == [2, 1, 2, 2] and r5["provenance"]["resumed_rows"] == 0


def test_resume_key_rejects_foreign_reports(small_spec):
    run_experiment(small_spec)
    report = json.loads(pathlib.Path(small_spec.out).read_text())
    assert len(completed_rows(report, report["provenance"]["resume_key"])) \
        == 2
    assert completed_rows(report, "not-the-key") == {}
    # truncated rows are never resumed (they must recompute)
    report["runs"][0]["truncated"] = True
    assert len(completed_rows(report, report["provenance"]["resume_key"])) \
        == 1


def test_resume_invalidated_by_artifact_retrain(tmp_path, monkeypatch):
    """Same spec text, retrained critic -> the resume key must change."""
    from repro.exp.provenance import artifact_provenance, resume_key
    monkeypatch.setenv("REPRO_ARTIFACTS", str(tmp_path))
    spec = ExperimentSpec(methods=("haf(critic=@critic)",),
                          scenarios=("paper",), seeds=(0,))
    save_critic(_tiny_critic(seed=0), tmp_path / "critic.json")
    key0 = resume_key(spec, artifact_provenance(spec))
    save_critic(_tiny_critic(seed=1), tmp_path / "critic.json")
    key1 = resume_key(spec, artifact_provenance(spec))
    assert key0 != key1
    assert spec.spec_hash() == spec.spec_hash()   # spec text unchanged


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #
def test_cli_spec_file_equals_raw_flags(tmp_path):
    methods = ("haf(agent=qwen3-32b-sim, critic=@critic?, label=HAF)",
               "haf-static(label=HAF-Static)")
    scenarios = ("paper(n_ai_requests=400, rho=1.0)",)
    spec = ExperimentSpec(methods=methods, scenarios=scenarios, seeds=(0,),
                          name="parity")
    path = spec.to_file(tmp_path / "parity.toml")

    ap = cli._build_parser()
    from_file = cli.build_experiment(ap.parse_args(["--spec", str(path)]))
    from_flags = cli.build_experiment(ap.parse_args(
        ["--methods", ",".join(methods),
         "--scenarios", ",".join(scenarios),
         "--seeds", "0,"]))
    assert from_file.expand() == from_flags.expand()
    assert from_file.identity_hash() == from_flags.identity_hash()


def test_cli_flags_override_spec_file(tmp_path):
    spec = ExperimentSpec(methods=("haf-static",), scenarios=("paper",),
                          seeds=(0,), workers=4)
    path = spec.to_file(tmp_path / "base.toml")
    ap = cli._build_parser()
    built = cli.build_experiment(ap.parse_args(
        ["--spec", str(path), "--seeds", "0..2", "--engine", "scalar",
         "--requests", "99", "--workers", "1"]))
    assert built.seeds == (0, 1, 2)
    assert built.engine == "scalar"
    assert built.n_ai_requests == 99
    assert built.workers == 1
    assert built.methods == spec.methods          # untouched by overrides


def test_cli_validate_runs_nothing(tmp_path, capsys):
    out = tmp_path / "never_written.json"
    rc = cli.main(["--validate", "--methods", "haf-static,round-robin",
                   "--scenarios", "paper", "--seeds", "2",
                   "--out", str(out)])
    assert rc == 0
    assert not out.exists()
    text = capsys.readouterr().out
    assert "validate only" in text and "nothing run" in text
    assert text.count("pending") == 4             # 2 methods x 2 seeds


def test_cli_seeds_zero_error_mentions_spec_grammar(capsys):
    with pytest.raises(SystemExit):
        cli.main(["--seeds", "0", "--methods", "haf-static",
                  "--scenarios", "paper"])
    err = capsys.readouterr().err
    assert "seed COUNT" in err and "spec file" in err


def test_cli_legacy_haf_llm_comma_error(capsys):
    with pytest.raises(SystemExit):
        cli.main(["--validate", "--scenarios", "paper",
                  "--methods", "haf-llm:curl -s x --data a, b"])
    err = capsys.readouterr().err
    assert 'haf-llm(cmd=' in err


# --------------------------------------------------------------------------- #
# the device engines: one process holds the chip, and nothing fails quietly
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ("jax", "pallas"))
def test_device_engine_with_workers_fails_before_any_job(engine,
                                                         monkeypatch):
    ran = []
    monkeypatch.setattr(sweep_mod, "run_job", ran.append)
    monkeypatch.setattr(sweep_mod, "run_batch_jobs",
                        lambda jobs, **kw: ran.append(jobs))
    spec = ExperimentSpec(methods=("haf-static",), scenarios=("paper",),
                          seeds=(0, 1), engine=engine, batch=2, workers=2)
    with pytest.raises(SpecError, match="workers must be 1"):
        spec.validate()
    with pytest.raises(ValueError, match="workers must be 1"):
        sweep_mod.run_sweep(spec.to_sweep_spec())
    with pytest.raises(SpecError, match="workers must be 1"):
        run_experiment(spec)
    assert ran == []


def test_cli_defaults_to_one_worker_for_device_engines():
    ap = cli._build_parser()
    for engine in ("jax", "pallas"):
        built = cli.build_experiment(ap.parse_args(
            ["--engine", engine, "--batch", "4"]))
        assert built.workers == 1
        built.validate()
    host = cli.build_experiment(ap.parse_args(["--engine", "numpy"]))
    assert host.workers == max(min(4, os.cpu_count() or 1), 1)


@pytest.mark.parametrize("fault,batch", (("none", 2), ("job", 1),
                                         ("batch", 2)))
def test_cli_exit_code_reports_failed_jobs(fault, batch, tmp_path,
                                           monkeypatch):
    real_job, real_batch = sweep_mod.run_job, sweep_mod.run_batch_jobs

    def flaky_job(job):
        if fault == "job" and job["seed"] == 1:
            raise RuntimeError("injected job failure")
        return real_job(job)

    def flaky_batch(jobs, fallback_note=None):
        if fault == "batch" and len(jobs) > 1:
            raise RuntimeError("injected batch failure")
        return real_batch(jobs, fallback_note=fallback_note)

    monkeypatch.setattr(sweep_mod, "run_job", flaky_job)
    monkeypatch.setattr(sweep_mod, "run_batch_jobs", flaky_batch)
    out = tmp_path / "r.json"
    rc = cli.main(["--methods", "haf-static", "--scenarios", "paper",
                   "--seeds", "2", "--requests", "60", "--workers", "1",
                   "--batch", str(batch), "--no-resume", "--out", str(out)])
    report = json.loads(out.read_text())
    fallbacks = [r for r in report["runs"] if r.get("batch_fallback")]
    assert report["n_failed"] == (fault == "job")
    assert len(fallbacks) == (2 if fault == "batch" else 0)
    assert rc == (0 if fault == "none" else 1)


def test_backend_info_names_the_device_for_device_engines():
    from repro.exp.provenance import backend_info
    host = backend_info("numpy")
    assert host["jax"] == jax.__version__ and "device" not in host
    devices = jax.devices()
    assert backend_info("jax")["device"] == {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}


# --------------------------------------------------------------------------- #
# mock LLM end-to-end (the haf-llm path with zero network)
# --------------------------------------------------------------------------- #
def test_mock_llm_script_contract():
    prompt = "\n".join([
        "Answer with a JSON array of at most 2 candidate identifiers.",
        'Example: ["mig:s12:n0->n1", "no-migration"]',
        "",
        "CANDIDATE ACTIONS (choose identifiers from this list only):",
        "  no-migration : keep the current placement",
        "  mig:s3:n0->n1 : move large0 n0->n1",
        "  mig:s1:n2->n0 : move small0 n2->n0",
    ])
    out = subprocess.run([sys.executable, str(MOCK_LLM)], input=prompt,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    # deterministic: first K-1 ids lexicographically + the hedge; the
    # example id from the preamble must NOT leak in
    assert json.loads(out.stdout) == ["mig:s1:n2->n0", "no-migration"]


def test_mock_llm_sweep_end_to_end():
    """haf-llm(cmd=...) drives a real sweep offline, reproducibly."""
    cmd = f"{sys.executable} {MOCK_LLM}"
    spec = ExperimentSpec(
        methods=(f'haf-llm(cmd="{cmd}", label=HAF-MockLLM)',),
        scenarios=("paper",), seeds=(0,), n_ai_requests=100)
    a = run_experiment(spec, resume=False)
    b = run_experiment(spec, resume=False)
    row_a, row_b = a["runs"][0], b["runs"][0]
    assert row_a["method"] == "HAF-MockLLM"
    assert 0.0 <= row_a["overall"] <= 1.0
    assert row_a["n_requests"] >= 100      # AI requests + the RAN stream
    for key in ("overall", "ran", "ai", "mig_total", "n_events"):
        assert row_a[key] == row_b[key], key

"""graftlint: lint-rule fixtures, baseline/suppression machinery, the
HLO program auditor, partition-rule coverage, and the repo-stays-clean
regression gate (this is the tier-1 lint gate itself)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import pytest

from raft_meets_dicl_tpu import parallel
from raft_meets_dicl_tpu.analysis import (
    envknobs, hlo, hostsync, lint, precision, tracerflow,
)

pytestmark = pytest.mark.lint

REPO = Path(__file__).parent.parent


def mk(source, rel="raft_meets_dicl_tpu/models/fixture.py"):
    src = textwrap.dedent(source)
    return lint.Module(rel, rel, src)


def run_fixture(tmp_path, source, baseline=None):
    """Run the full lint pipeline over a one-file tree (suppression +
    baseline resolution included, unlike calling a rule check directly)."""
    (tmp_path / "main.py").write_text(textwrap.dedent(source))
    return lint.run(tmp_path, baseline=baseline, targets=("main.py",))


# -- host-sync ---------------------------------------------------------------


def test_hostsync_error_when_jit_reachable():
    m = mk("""
        import jax

        @jax.jit
        def step(state, batch):
            loss = state.apply(batch)
            return float(loss)
        """)
    found = hostsync.check(m)
    assert [f.severity for f in found] == ["error"]
    assert "jit-reachable" in found[0].message


def test_hostsync_reachable_through_helper_and_scan_body():
    m = mk("""
        import jax
        from jax import lax

        def fetch(x):
            return x.item()

        def body(carry, x):
            return carry, fetch(x)

        @jax.jit
        def step(xs):
            return lax.scan(body, 0, xs)
        """)
    errors = [f for f in hostsync.check(m) if f.severity == "error"]
    assert len(errors) == 1 and ".item()" in errors[0].message


def test_hostsync_warn_off_hot_path_and_registry_roots():
    m = mk("""
        import jax

        def summary(metrics):
            return float(metrics)

        def _train(state, batch):
            return state.apply(batch).item()

        register_step("train_step", _train)
        """)
    found = {f.severity for f in hostsync.check(m)}
    assert found == {"warn", "error"}  # summary warns, _train errors


def test_hostsync_skips_jax_free_modules_and_literals():
    clean = mk("""
        import numpy as np

        def parse(cfg):
            return float(cfg), float("nan"), np.asarray(cfg)
        """)
    assert hostsync.check(clean) == []
    jaxy = mk("""
        import jax

        def parse(args):
            return float(args.lr), int("3")
        """)
    assert hostsync.check(jaxy) == []  # attr chains + literals pass


def test_hostsync_suppression_resolves(tmp_path):
    rep = run_fixture(tmp_path, """
        import jax

        def summary(x):
            return float(x)  # graftlint: disable=host-sync -- eval table, post-step
        """)
    assert rep.ok
    assert [f.status for f in rep.findings] == ["suppressed"]
    assert rep.findings[0].justification == "eval table, post-step"


# -- tracer-branch -----------------------------------------------------------


def test_tracerbranch_flags_data_dependent_if():
    m = mk("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.abs(x)
            if y > 0:
                return y
            return x
        """)
    found = tracerflow.check(m)
    assert len(found) == 1 and found[0].rule == "tracer-branch"


def test_tracerbranch_static_attrs_and_shields_pass():
    m = mk("""
        import jax

        @jax.jit
        def f(x, mode=None):
            if x.ndim > 3:
                x = x[0]
            if isinstance(mode, str):
                x = x + 1
            if mode is None:
                x = x * 2
            while x.shape[0] > 4:
                x = x[::2]
            return x
        """)
    assert tracerflow.check(m) == []


def test_tracerbranch_ignores_host_functions():
    m = mk("""
        import jax

        def config(v):
            if v > 0:
                return v
            return -v
        """)
    assert tracerflow.check(m) == []


# -- f32-literal -------------------------------------------------------------


def test_precision_flags_dtypeless_and_explicit_f32():
    m = mk("""
        import flax.linen as nn
        import jax.numpy as jnp

        class Net(nn.Module):
            mixed_precision: bool = False

            def __call__(self, x):
                a = jnp.zeros((4,))
                b = jnp.ones((4,), dtype=jnp.float32)
                c = jnp.zeros((4,), dtype=jnp.bfloat16)
                return a + b + c
        """)
    found = precision.check(m)
    assert len(found) == 2
    assert "dtype-less" in found[0].message
    assert "jnp.float32" in found[1].message


def test_precision_scope_needs_policy_and_models_path():
    src = """
        import flax.linen as nn
        import jax.numpy as jnp

        class Net(nn.Module):
            features: int = 8

            def __call__(self, x):
                return x + jnp.zeros((4,))
        """
    assert precision.check(mk(src)) == []  # no policy field
    m = mk(src.replace("features: int = 8", "dtype: str = None"),
           rel="raft_meets_dicl_tpu/ops/fixture.py")
    assert precision.check(m) == []  # not under models/


# -- env-knob / env-docs -----------------------------------------------------


def test_envknob_flags_reads_not_writes():
    m = mk("""
        import os

        v = os.environ.get("RMD_TELEMETRY")
        w = os.environ["RMD_AOT"]
        armed = "RMD_FAULT" in os.environ
        os.environ["RMD_FAULT"] = "decode:1"   # write: legal
        del os.environ["RMD_FAULT"]            # delete: legal
        """)
    found = envknobs.check(m)
    assert len(found) == 3
    msgs = " ".join(f.message for f in found)
    for name in ("RMD_TELEMETRY", "RMD_AOT", "RMD_FAULT"):
        assert name in msgs
    assert all("utils.env" in f.message for f in found)


def _env_module_stub():
    # the project checks only engage when the linted tree contains the
    # knob registry itself
    return mk("KNOBS = {}\n", rel=envknobs.ENV_MODULE)


def test_envknob_project_catches_typo_and_stale(tmp_path):
    m = mk("""
        from raft_meets_dicl_tpu.utils import env

        x = env.get_bool("RMD_PREFTCH")
        """)
    ctx = lint.ProjectContext(tmp_path, [m, _env_module_stub()])
    found = envknobs.check_project(ctx)
    typos = [f for f in found if "RMD_PREFTCH" in f.message]
    assert len(typos) == 1 and "unregistered" in typos[0].message
    # with only this module in scope, real knobs are unreferenced = stale
    assert any("stale knob" in f.message for f in found)


def test_envknob_dead_rule_needs_an_accessor_read(tmp_path):
    # a knob that is written, saved/restored, and name-dropped in a
    # docstring is still *dead* until something reads it through a
    # typed accessor — this is what separates env-dead-knob from the
    # reference check in check_project
    knob = next(iter(_real_knobs()))
    mentions_only = mk(f"""
        import os

        def save_restore():
            '''round-trips {knob} around a fault drill'''
            old = os.environ.pop("{knob}", None)
            os.environ["{knob}"] = "1"
        """)
    ctx = lint.ProjectContext(tmp_path, [mentions_only, _env_module_stub()])
    dead = {f.message.split(":")[0] for f in envknobs.check_dead_knobs(ctx)}
    assert f"dead knob {knob}" in dead

    reader = mk(f"""
        from raft_meets_dicl_tpu.utils import env

        flag = env.get_bool("{knob}")
        """, rel="raft_meets_dicl_tpu/models/reader.py")
    ctx = lint.ProjectContext(
        tmp_path, [mentions_only, reader, _env_module_stub()])
    dead = {f.message.split(":")[0] for f in envknobs.check_dead_knobs(ctx)}
    assert f"dead knob {knob}" not in dead
    # every finding names the registry module, not the mentioning file
    for f in envknobs.check_dead_knobs(ctx):
        assert f.path == envknobs.ENV_MODULE

    # a direct environ read keeps the knob live too (it already draws
    # its own env-knob finding; no double jeopardy)
    env_reader = mk(f"""
        import os

        raw = os.environ.get("{knob}")
        """, rel="raft_meets_dicl_tpu/models/envreader.py")
    ctx = lint.ProjectContext(
        tmp_path, [mentions_only, env_reader, _env_module_stub()])
    dead = {f.message.split(":")[0] for f in envknobs.check_dead_knobs(ctx)}
    assert f"dead knob {knob}" not in dead


def _real_knobs():
    from raft_meets_dicl_tpu.utils import env
    return env.KNOBS


def test_envdocs_detects_missing_and_stale_table(tmp_path):
    from raft_meets_dicl_tpu.utils import env

    ctx = lint.ProjectContext(tmp_path, [_env_module_stub()])
    readme = tmp_path / "README.md"
    readme.write_text("# no markers\n")
    assert any("markers missing" in f.message
               for f in envknobs.check_docs(ctx))
    readme.write_text(f"{env.TABLE_BEGIN}\nstale\n{env.TABLE_END}\n")
    assert any("stale" in f.message for f in envknobs.check_docs(ctx))
    readme.write_text(
        f"{env.TABLE_BEGIN}\n{env.readme_table()}\n{env.TABLE_END}\n")
    assert envknobs.check_docs(ctx) == []


# -- framework: suppressions, baseline, report -------------------------------


def test_bad_suppression_missing_reason_and_unknown_rule(tmp_path):
    rep = run_fixture(tmp_path, """
        import jax

        def f(x):
            return float(x)  # graftlint: disable=host-sync

        y = 1  # graftlint: disable=no-such-rule -- because
        """)
    # the reason-less pragma still suppresses its line, but the gate
    # fails anyway: bad-suppression findings are never suppressible
    assert sorted(f.rule for f in rep.open) == ["bad-suppression",
                                               "bad-suppression"]
    assert not rep.ok
    assert [f.status for f in rep.findings
            if f.rule == "host-sync"] == ["suppressed"]


def test_baseline_requires_justification_and_reports_stale(tmp_path):
    with pytest.raises(ValueError, match="justification"):
        lint.Baseline([{"rule": "host-sync", "glob": "*"}])
    bl = lint.Baseline([
        {"rule": "host-sync", "glob": "main.py",
         "justification": "grandfathered"},
        {"rule": "host-sync", "glob": "never/*",
         "justification": "matches nothing"},
    ])
    rep = run_fixture(tmp_path, """
        import jax

        def f(x):
            return float(x)
        """, baseline=bl)
    assert rep.ok
    assert [f.status for f in rep.findings] == ["baselined"]
    assert [e["glob"] for e in rep.stale_baseline] == ["never/*"]


def test_baseline_version_gate(tmp_path):
    p = tmp_path / "bl.json"
    p.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError, match="version"):
        lint.Baseline.load(p)


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "main.py").write_text("def broken(:\n")
    rep = lint.run(tmp_path, baseline=lint.Baseline.empty(),
                   targets=("main.py",))
    assert [f.rule for f in rep.findings] == ["parse-error"]


def test_emit_events_schema(tmp_path):
    from raft_meets_dicl_tpu import telemetry
    from raft_meets_dicl_tpu.telemetry import report as trep

    rep = run_fixture(tmp_path, """
        import jax

        def f(x):
            return float(x)
        """)
    tele = telemetry.Telemetry()   # in-memory
    lint.emit_events(rep, tele)
    assert [e["kind"] for e in tele.events] == ["lint"]
    stats = trep.lint_stats(tele.events)
    assert stats["per_rule"]["host-sync"]["open"] == 1
    assert stats["open"][0]["path"] == "main.py"


# -- the repo gate -----------------------------------------------------------


def test_repo_is_lint_clean_with_committed_baseline():
    rep = lint.run(REPO)
    assert rep.n_modules > 100
    open_ = [f.location + " " + f.rule for f in rep.open]
    assert open_ == [], f"new lint findings: {open_}"
    stale = [(e["rule"], e["glob"]) for e in rep.stale_baseline]
    assert stale == [], f"stale baseline entries: {stale}"


@pytest.mark.slow
def test_cli_exit_codes(tmp_path):
    script = REPO / "scripts" / "graftlint.py"
    (tmp_path / "main.py").write_text(
        "import jax\n\ndef f(x):\n    return float(x)\n")
    bad = subprocess.run(
        [sys.executable, str(script), "--root", str(tmp_path), "--json"],
        capture_output=True, text=True)
    assert bad.returncode == 1
    payload = json.loads(bad.stdout)
    assert payload["ok"] is False and payload["open"] >= 1
    (tmp_path / "main.py").write_text("x = 1\n")
    good = subprocess.run(
        [sys.executable, str(script), "--root", str(tmp_path)],
        capture_output=True, text=True)
    assert good.returncode == 0, good.stdout + good.stderr


def _graftlint_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graftlint_cli", REPO / "scripts" / "graftlint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


HOTSYNC_SRC = "import jax\n\ndef f(x):\n    return float(x)\n"


def test_prune_drops_only_stale_baseline_entries(tmp_path):
    cli = _graftlint_cli()
    (tmp_path / "main.py").write_text(HOTSYNC_SRC)
    path = tmp_path / lint.BASELINE_NAME
    path.write_text(json.dumps({
        "version": 1,
        "comment": "header note that must survive the rewrite",
        "entries": [
            {"rule": "host-sync", "glob": "main.py",
             "justification": "grandfathered"},
            {"rule": "host-sync", "glob": "gone/*.py",
             "justification": "module deleted two PRs ago"},
        ],
    }))
    assert cli.prune_baseline(tmp_path, str(path)) == 0
    data = json.loads(path.read_text())
    # only the entry that matched nothing is gone; header rides through
    assert [e["glob"] for e in data["entries"]] == ["main.py"]
    assert data["comment"] == "header note that must survive the rewrite"
    assert data["version"] == 1
    # idempotent: a second prune is a no-op
    before = path.read_text()
    assert cli.prune_baseline(tmp_path, str(path)) == 0
    assert path.read_text() == before
    # the pruned baseline still fully suppresses the tree
    rep = lint.run(tmp_path, baseline=lint.Baseline.load(path))
    assert rep.ok and len(rep.baselined) == 1 and not rep.stale_baseline


def test_json_report_schema_and_exit_code_contract(tmp_path):
    cli = _graftlint_cli()
    bad = run_fixture(tmp_path, HOTSYNC_SRC)
    payload = cli.json_report(bad)
    assert payload["schema"] == 1
    assert payload["ok"] is False and payload["exit_code"] == 1
    assert payload["open"] >= 1
    f = payload["findings"][0]
    assert {"rule", "path", "line", "severity", "status",
            "message"} <= set(f)
    json.dumps(payload)  # must be serializable as-is

    good = run_fixture(tmp_path, "x = 1\n")
    payload = cli.json_report(good)
    assert payload["ok"] is True and payload["exit_code"] == 0
    assert payload["stale_baseline_entries"] == []
    # --hlo attaches program reports under a dedicated key
    payload = cli.json_report(good, hlo_reports=[{"program": "p"}])
    assert payload["hlo"] == [{"program": "p"}]


# -- HLO auditor -------------------------------------------------------------


STABLEHLO_FIXTURE = """
module @jit_step {
  func.func public @main(%arg0: tensor<8x16xf32>) -> tensor<8x16xf32> {
    %0 = stablehlo.constant dense<1.0> : tensor<1024x1024xf32> loc("x")
    %1 = stablehlo.all_reduce(%arg0) : tensor<8x16xf32> loc("y")
    %2 = stablehlo.convolution(%arg0, %arg0) : (tensor<8x16xf32>,
         tensor<8x16xf32>) -> tensor<8x16xf32>
    return %2 : tensor<8x16xf32>
  }
}
#loc = loc("step")
"""


def test_audit_stablehlo_counts():
    out = hlo.audit_stablehlo(STABLEHLO_FIXTURE)
    assert out["collectives"] == {"all-reduce": 1}
    assert out["f32_convolutions"] == 1
    assert out["large_constants"] == [
        {"type": "tensor<1024x1024xf32>", "bytes": 4 * 1024 * 1024}]


def test_fingerprint_ignores_locations_only():
    moved = STABLEHLO_FIXTURE.replace('loc("x")', 'loc("elsewhere")')
    assert hlo.fingerprint(STABLEHLO_FIXTURE) == hlo.fingerprint(moved)
    changed = STABLEHLO_FIXTURE.replace("dense<1.0>", "dense<2.0>")
    assert hlo.fingerprint(STABLEHLO_FIXTURE) != hlo.fingerprint(changed)


def test_audit_compiled_counts_rhs_ops_only():
    text = textwrap.dedent("""
        %ar = f32[8] all-reduce(%x), replica_groups={}
        %ag = f32[16] all-gather(%y), dimensions={0}
        ROOT %t = (f32[8]) tuple(%ar)
        all-reduce-free comment line
        """)
    assert hlo.audit_compiled(text) == {"all-reduce": 1, "all-gather": 1}


def test_audit_registry_flagship_programs():
    """The acceptance gate: every registered flagship program lowers with
    a stable fingerprint and sane collective counts, and the audit emits
    zero findings."""
    reports, findings = hlo.audit_registry(n_devices=2, shape=(48, 64))
    assert findings == []
    assert len(reports) == 2
    train = next(r for r in reports if "train_step" in r["key"])
    next(r for r in reports if "eval_step" in r["key"])
    for r in reports:
        assert r["fingerprint_stable"], r["key"]
        assert r["large_constants"] == []
    # 2-device data-parallel train step must sync gradients
    assert sum(train["compiled_collectives"].values()) > 0
    rendered = hlo.render_reports(reports)
    assert "hlo audit" in rendered and "stable" in rendered


# -- partition-rule coverage (satellite of the lint PR) ----------------------


@pytest.mark.spmd
def test_partitioner_coverage_flags_dead_rules():
    from jax.sharding import PartitionSpec as P

    mesh = parallel.make_mesh((4, 2))
    params = {"FeatureEncoder_0": {"Conv_0": {"kernel": jnp.zeros((3, 3, 8, 16)),
                                              "bias": jnp.zeros((16,))}}}
    part = parallel.Partitioner(mesh)
    cov = part.coverage(params)
    assert cov["n_paths"] == 2
    assert cov["unmatched"] == []
    # encoder rule matches; the dead ones are update/flow-head rules that
    # this toy tree never instantiates
    matches = dict(cov["rule_matches"])
    assert matches[r"(FeatureEncoder|StackEncoder|PoolEncoder|Rfpm)"
                   r"[^/]*/.*kernel$"] == 1
    assert len(cov["dead_rules"]) == 2

    bogus = parallel.Partitioner(
        mesh, rules=((r"NoSuchModule/.*kernel$", P("model")), (r".*", P())))
    cov = bogus.coverage(params)
    assert cov["dead_rules"] == [r"NoSuchModule/.*kernel$"]
    assert cov["unmatched"] == []


@pytest.mark.spmd
def test_shard_state_warns_on_dead_rules():
    import optax
    from jax.sharding import PartitionSpec as P

    mesh = parallel.make_mesh((4, 2))
    variables = {"params": {"Dense_0": {"kernel": jnp.zeros((8, 8))}}}
    tx = optax.sgd(1e-3)
    state = parallel.TrainState.create(variables, tx)
    part = parallel.Partitioner(
        mesh, rules=((r"Ghost/.*kernel$", P("model")), (r".*", P())))
    with pytest.warns(UserWarning, match="dead rules"):
        part.shard_state(state)

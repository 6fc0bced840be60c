"""Tests for the static-analysis subsystem (``apex_tpu/analysis/``).

Each pass gets a known-bad fixture (planted host transfer, dropped
donation, silent amp promotion, f64 literal, retrace, wrong collective
count) asserted to produce EXACTLY the expected rule id, plus a
clean-step fixture asserted to produce zero findings — the acceptance
contract of ISSUE 4, and the same properties ``tools/graph_lint.py``
gates in ``tools/verify_tier1.sh``.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import analysis
from apex_tpu.analysis import hlo as hlo_lib


# ---------------------------------------------------------------------------
# transfer lint
# ---------------------------------------------------------------------------


def test_planted_debug_print_is_caught():
    def step(x):
        jax.debug.print("loss={x}", x=x.sum())
        return x * 2.0

    report = analysis.check(step, jnp.zeros((8,), jnp.float32))
    assert "transfer-callback" in report.rule_ids()
    # the callback also survives into compiled HLO as a custom-call
    assert "transfer-hlo-host" in report.rule_ids()
    assert not report.ok()


def test_planted_pure_callback_is_caught():
    def step(x):
        y = jax.pure_callback(
            lambda v: v * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y + 1.0

    report = analysis.check(
        step, jnp.zeros((4,), jnp.float32), rules=("transfer",)
    )
    assert "transfer-callback" in report.rule_ids()


def test_callback_inside_scan_body_is_caught():
    """A transfer buried in a scan body fires every iteration — the
    recursive jaxpr walk must find it."""
    def step(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c[0])
            return c + 1.0, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    report = analysis.check(
        step, jnp.zeros((4,), jnp.float32), rules=("transfer",)
    )
    assert "transfer-callback" in report.rule_ids()


# ---------------------------------------------------------------------------
# promotion lint
# ---------------------------------------------------------------------------


def test_planted_silent_promotion_is_caught():
    """bf16 activations meeting a NON-weak f32 constant silently widen
    the whole downstream subgraph — the classic amp leak."""
    def step(x):
        return (x * jnp.float32(2.0)).sum()

    report = analysis.check(
        step, jnp.zeros((8,), jnp.bfloat16), policy=jnp.bfloat16
    )
    assert report.rule_ids() == ["promotion-widen"]


def test_weak_literal_does_not_flag():
    """A python-float literal is weakly typed: bf16 * 2.0 stays bf16 —
    nothing to flag."""
    def step(x):
        return (x * 2.0).sum()

    report = analysis.check(
        step, jnp.zeros((8,), jnp.bfloat16), policy=jnp.bfloat16
    )
    assert report.findings == []


def test_named_scope_marks_widening_intentional():
    def step(x):
        with jax.named_scope("f32_accum"):
            acc = x.astype(jnp.float32)
        return (acc * acc).sum()

    report = analysis.check(
        step, jnp.zeros((8,), jnp.bfloat16), policy=jnp.bfloat16
    )
    assert report.findings == []


def test_reduction_upcast_idiom_is_exempt():
    """jnp.sum on bf16 internally accumulates in f32 then narrows —
    by-design precision, not a silent promotion."""
    def step(x):
        return jnp.sum(x)

    report = analysis.check(
        step, jnp.zeros((64,), jnp.bfloat16), policy=jnp.bfloat16
    )
    assert report.findings == []


def test_planted_f64_is_caught():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x * jnp.float64(3.0)
        )(jnp.zeros((4,), jnp.float64))
    report = analysis.lint_jaxpr(jaxpr)
    assert report.rule_ids() == ["promotion-f64"]
    assert not report.ok()


# ---------------------------------------------------------------------------
# donation lint
# ---------------------------------------------------------------------------


def test_planted_dropped_donation_is_caught():
    # both donated buffers are size-reduced away: no output matches,
    # XLA cannot alias either one
    def step(x, y):
        return jnp.sum(x) + jnp.sum(y)

    report = analysis.check(
        step, jnp.zeros((64,), jnp.float32), jnp.ones((32,), jnp.float32),
        donate_argnums=(0, 1),
    )
    assert report.rule_ids() == ["donation-dropped"]
    finding = report.by_rule("donation-dropped")[0]
    assert "2 of 2" in finding.message


def test_clean_donation_passes():
    def step(state):
        return {k: v + 1.0 for k, v in state.items()}

    state = {"w": jnp.zeros((16, 16)), "m": jnp.zeros((16, 16))}
    report = analysis.check(step, state, donate_argnums=(0,))
    assert report.findings == []


def test_input_output_alias_parser():
    header = (
        "HloModule jit_f, is_scheduled=true, input_output_alias={ "
        "{0}: (0, {}, may-alias), {1, 2}: (3, {}, must-alias) }, "
        "entry_computation_layout={(f32[8]{0})->f32[8]{0}}"
    )
    aliases = hlo_lib.input_output_aliases(header)
    assert aliases == [(0, "0"), (3, "1, 2")]
    assert hlo_lib.input_output_aliases("HloModule jit_g") == []


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------


def test_retrace_flagged_on_shape_change():
    s = analysis.RetraceSentinel()
    assert s.observe(jnp.zeros((8,), jnp.float32)) is None
    assert s.observe(jnp.zeros((8,), jnp.float32)) is None  # same sig
    finding = s.observe(jnp.zeros((16,), jnp.float32))  # planted retrace
    assert finding is not None and finding.rule == "retrace"
    assert s.retraces == 1
    assert "leaf 0" in finding.message


def test_retrace_flagged_on_static_value_change():
    s = analysis.RetraceSentinel()
    assert s.observe(jnp.zeros((4,)), flag=True) is None
    f = s.observe(jnp.zeros((4,)), flag=False)
    assert f is not None and f.rule == "retrace"


def test_retrace_allowed_budget():
    s = analysis.RetraceSentinel(allowed=2)
    assert s.observe(jnp.zeros((8,))) is None
    assert s.observe(jnp.zeros((7,))) is None  # ragged tail, budgeted
    assert s.observe(jnp.zeros((6,))) is not None


def test_retrace_steady_state_never_flags():
    s = analysis.RetraceSentinel()
    for _ in range(10):
        assert s.observe({"w": jnp.zeros((4, 4))}, jnp.zeros((4,))) is None
    assert s.retraces == 0 and s.calls == 10


# ---------------------------------------------------------------------------
# collective consistency
# ---------------------------------------------------------------------------

_AR_HLO = """
ENTRY %main {
  %p0 = f32[8,128]{1,0} parameter(0)
  %ar = f32[8,128]{1,0} all-reduce(%p0), replica_groups={{0,1}}
  ROOT %out = f32[8,128]{1,0} add(%ar, %ar)
}
"""


def test_planted_wrong_collective_count_is_caught():
    report = analysis.lint_hlo(
        _AR_HLO, expect_collectives={"all-reduce": 2}
    )
    assert report.rule_ids() == ["collective-count"]


def test_collective_dtype_and_bytes_checks():
    report = analysis.lint_hlo(
        _AR_HLO,
        expect_collectives={
            "all-reduce": {"count": 1, "dtypes": ["s8"], "bytes": 17}
        },
    )
    assert report.rule_ids() == ["collective-bytes", "collective-dtype"]
    clean = analysis.lint_hlo(
        _AR_HLO,
        expect_collectives={
            "all-reduce": {
                "count": 1, "dtypes": ["f32"], "bytes": 8 * 128 * 4,
            }
        },
    )
    assert clean.findings == []


def test_collective_count_live_on_mesh(eight_devices):
    """End to end on a real compiled program: one psum over the
    8-device mesh must be exactly one all-reduce."""
    mesh = Mesh(eight_devices, ("dp",))

    def step(x):
        return jax.lax.psum(x, "dp")

    fn = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
            check_vma=False,
        )
    )
    x = jnp.zeros((8, 16), jnp.float32)
    ok = analysis.check(fn, x, expect_collectives={"all-reduce": 1})
    assert ok.findings == []
    bad = analysis.check(fn, x, expect_collectives={"all-reduce": 3})
    assert bad.rule_ids() == ["collective-count"]


# ---------------------------------------------------------------------------
# host-transfer HLO scan
# ---------------------------------------------------------------------------


def test_host_transfer_ops_scan():
    hlo = """
ENTRY %main {
  %tok = token[] after-all()
  %in = ((f32[8]{0}), token[]) infeed(%tok)
  %cc = () custom-call(s64[] %c, f32[8]{0} %x), custom_call_target="xla_python_cpu_callback", api_version=API_VERSION_STATUS_RETURNING
  %send = (f32[8]{0}, u32[], token[]) send(%x, %tok), channel_id=1, is_host_transfer=true
  %benign = f32[8]{0} custom-call(%x), custom_call_target="Sharding"
}
"""
    found = hlo_lib.host_transfer_ops(hlo)
    kinds = sorted(why for _name, why in found)
    assert len(found) == 3
    assert kinds[0] == "callback custom-call (xla_python_cpu_callback)"
    assert "host send/recv" in kinds
    assert "infeed" in kinds


# ---------------------------------------------------------------------------
# the clean-step fixture: a full guarded train step with zero findings
# ---------------------------------------------------------------------------


def test_clean_step_produces_zero_findings():
    """A well-formed train step — donated state, policy-conformant
    dtypes, no callbacks — must come back clean on every pass."""
    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state, batch)
        return (
            {k: state[k] - 0.1 * grads[k] for k in state},
            loss,
        )

    state = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    batch = (jnp.ones((16, 8)), jnp.ones((16, 4)))
    report = analysis.check(
        step, state, batch,
        policy=jnp.float32, donate_argnums=(0,),
        name="clean_step",
    )
    assert report.findings == [], report.render()
    assert report.ok() and report.ok(fail_on="warning")


# ---------------------------------------------------------------------------
# report plumbing: JSON schema, catalog integrity, board publishing
# ---------------------------------------------------------------------------


def test_every_rule_is_cataloged_and_catalog_is_complete():
    assert set(analysis.RULES) == {
        "transfer-callback", "transfer-hlo-host",
        "promotion-f64", "promotion-widen",
        "donation-dropped", "retrace",
        "collective-count", "collective-bytes", "collective-dtype",
        "sharding-replicated", "sharding-mismatch",
        "sharding-unverified", "reshard-unplanned", "reshard-plan",
        "memory-budget", "memory-pool-copy",
        "sharding-implicit-replication",
        "sharding-missing-constraint",
        "kernel-vmem-overflow", "kernel-tile-misaligned",
        "kernel-grid-oob", "kernel-block-race", "kernel-dead-tiles",
        "kernel-hardcoded-block",
        "race-unlocked-shared-state", "race-nonatomic-counter",
        "race-lock-across-blocking",
        "replay-wall-clock", "replay-unseeded-rng",
        "replay-set-order", "replay-env-read",
    }
    for rule, (sev, desc, hint) in analysis.RULES.items():
        assert sev in (analysis.ERROR, analysis.WARNING, analysis.INFO)
        assert desc and hint
    with pytest.raises(KeyError):
        analysis.make_finding("not-a-rule", path="", message="")


def test_report_json_roundtrip_and_severity_gate():
    f1 = analysis.make_finding("promotion-widen", path="p", message="m")
    f2 = analysis.make_finding("donation-dropped", path="q", message="n")
    report = analysis.Report([f1, f2], target="t", rules_run=("promotion",))
    blob = json.loads(report.to_json_line())
    assert blob["target"] == "t"
    assert blob["errors"] == 1 and blob["warnings"] == 1
    assert blob["findings"][0]["rule"] == "promotion-widen"
    assert not report.ok()  # one error
    warn_only = analysis.Report([f1])
    assert warn_only.ok()  # warnings pass the default gate
    assert not warn_only.ok(fail_on="warning")


def test_publish_report_rides_the_board():
    from apex_tpu.observability.metrics import board

    board.clear()
    report = analysis.Report(
        [analysis.make_finding("retrace", path="", message="x")],
        target="pub",
    )
    analysis.publish_report(report)
    snap = board.snapshot()
    assert snap["analysis/errors"] == 1
    assert snap["analysis/warnings"] == 0
    assert snap["analysis/rule/retrace"] == 1
    board.clear()


def test_unknown_rule_selector_raises():
    with pytest.raises(ValueError):
        analysis.check(lambda x: x, jnp.zeros(()), rules=("bogus",))


# ---------------------------------------------------------------------------
# the lint passes on our own codebase (ISSUE 4 satellite: contrib/ops)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["layer_norm", "softmax", "xentropy",
                                  "focal_loss", "group_norm"])
def test_own_ops_are_promotion_clean_under_bf16(name):
    """The promotion lint must pass on our own fused ops and contrib
    stubs: their f32 accumulation regions are marked policy-exempt
    (named scopes), so a bf16 policy sees zero findings."""
    from apex_tpu import ops
    from apex_tpu.contrib.focal_loss import sigmoid_focal_loss
    from apex_tpu.contrib.group_norm import group_norm

    bf = jnp.bfloat16
    x = jnp.ones((4, 64), bf)
    builders = {
        "layer_norm": lambda: jax.make_jaxpr(
            lambda x: jax.grad(
                lambda xx: ops.fused_layer_norm_affine(
                    xx, jnp.ones((64,), bf), jnp.zeros((64,), bf), 64
                ).sum()
            )(x).sum()
        )(x),
        "softmax": lambda: jax.make_jaxpr(
            lambda s: jax.grad(
                lambda ss: ops.scaled_masked_softmax(
                    ss, ss > 2, 2.0
                ).sum()
            )(s).sum()
        )(jnp.ones((2, 2, 8, 8), bf)),
        "xentropy": lambda: jax.make_jaxpr(
            lambda l: jax.grad(
                lambda ll: ops.softmax_cross_entropy_loss(
                    ll, jnp.zeros((8,), jnp.int32)
                ).sum()
            )(l).sum()
        )(jnp.ones((8, 32), bf)),
        "focal_loss": lambda: jax.make_jaxpr(
            lambda l: sigmoid_focal_loss(l, jnp.zeros((4, 10), bf)).sum()
        )(jnp.ones((4, 10), bf)),
        "group_norm": lambda: jax.make_jaxpr(
            lambda x: group_norm(x.reshape(4, 8, 8), 4).sum()
        )(x),
    }
    report = analysis.lint_jaxpr(
        builders[name](), policy=bf, name=f"ops/{name}"
    )
    assert report.findings == [], report.render()


# ---------------------------------------------------------------------------
# sharding & memory passes (ISSUE 9): rule tables, spec conformance,
# resharding plan, static peak-HBM budget
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from apex_tpu.analysis import memory as memory_lib  # noqa: E402
from apex_tpu.analysis import sharding as sharding_lib  # noqa: E402


def _dp_tp_mesh(eight_devices):
    return Mesh(np.array(eight_devices[:4]).reshape(2, 2), ("dp", "tp"))


_DPTP = {"dp": 2, "tp": 2}


class TestRuleTables:
    def test_match_partition_rules_first_match_and_scalar_exempt(self):
        rules = [(r"\bw$", P(None, "tp")), (r".*", P())]
        params = {
            "w": jnp.zeros((8, 8)),
            "b": jnp.zeros((8,)),
            "count": jnp.zeros(()),  # scalar: never partitioned
        }
        specs = analysis.match_partition_rules(rules, params)
        assert specs["w"] == P(None, "tp")
        assert specs["b"] == P()
        assert specs["count"] == P()

    def test_match_partition_rules_hole_raises(self):
        with pytest.raises(ValueError, match="partition rule not found"):
            analysis.match_partition_rules(
                [(r"\bw$", P())], {"other": jnp.zeros((4, 4))}
            )

    def test_normalize_param_path_matches_tree_paths(self):
        """ONE rule table serves the live pytree and the compiled
        module: HLO op_name metadata normalizes to the same /-joined
        path tree_paths produces."""
        assert sharding_lib.normalize_param_path(
            "state[\\'params\\'][\\'w\\']"
        ) == "state/params/w"
        assert sharding_lib.normalize_param_path("batch[0]") == "batch/0"
        assert sharding_lib.normalize_param_path(
            "scaler_state.loss_scale"
        ) == "scaler_state/loss_scale"
        paths = [p for p, _l in sharding_lib.tree_paths(
            {"state": {"params": {"w": jnp.zeros((2,))}}}
        )]
        assert paths == ["state/params/w"]

    def test_parse_sharding_variants(self):
        ps_ = hlo_lib.parse_sharding
        assert ps_("replicated")["kind"] == "replicated"
        assert ps_("maximal device=3")["kind"] == "maximal"
        assert ps_("devices=[2,4]<=[8]") == {
            "kind": "tiled", "dims": [2, 4]}
        assert ps_(
            "devices=[1,4,2]<=[2,4]T(1,0) last_tile_dim_replicate"
        ) == {"kind": "tiled", "dims": [1, 4]}
        # tiled-in-name-only = replicated
        assert ps_(
            "devices=[1,1,8]<=[8] last_tile_dim_replicate"
        )["kind"] == "replicated"
        assert ps_(None)["kind"] == "unknown"

    def test_mesh_axis_groups_row_major(self):
        groups = sharding_lib.mesh_axis_groups({"dp": 2, "tp": 4})
        assert groups["tp"] == frozenset([
            frozenset({0, 1, 2, 3}), frozenset({4, 5, 6, 7})])
        assert groups["dp"] == frozenset([
            frozenset({0, 4}), frozenset({1, 5}),
            frozenset({2, 6}), frozenset({3, 7})])
        assert groups["all"] == frozenset([frozenset(range(8))])

    def test_iota_replica_groups_disambiguate_equal_axes(self):
        """XLA's compact iota form must still attribute axes EXACTLY
        at dp=tp=2, where group size alone is ambiguous: the minor
        (tp) axis prints untransposed rows, the major (dp) axis a
        T(1,0) iota — both must resolve, never fall back to None."""
        mesh = {"dp": 2, "tp": 2}
        groups = sharding_lib.mesh_axis_groups(mesh)

        def _coll(line):
            recs = hlo_lib.collective_instructions(
                "ENTRY %main {\n  " + line + "\n}"
            )
            assert len(recs) == 1
            return recs[0]

        tp = _coll("%ar = f32[8]{0} all-reduce(f32[8]{0} %x), "
                   "replica_groups=[2,2]<=[4], to_apply=%add")
        assert tp["groups"] == [[0, 1], [2, 3]]
        assert sharding_lib.infer_collective_axis(
            tp, groups, mesh) == "tp"
        dp = _coll("%ar = f32[8]{0} all-reduce(f32[8]{0} %x), "
                   "replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add")
        assert dp["groups"] == [[0, 2], [1, 3]]
        assert sharding_lib.infer_collective_axis(
            dp, groups, mesh) == "dp"
        allg = _coll("%ar = f32[8]{0} all-reduce(f32[8]{0} %x), "
                     "replica_groups=[1,4]<=[4], to_apply=%add")
        assert sharding_lib.infer_collective_axis(
            allg, groups, mesh) == "all"


class TestShardingConformance:
    RULES = [(r"\bw$", P(None, "tp")), (r"\bb$", P()), (r"^x", P("dp", None))]

    def _step(self):
        def step(params, x):
            return jnp.tanh(x @ params["w"] + params["b"]).sum()
        params = {
            "w": jnp.zeros((64, 64), jnp.float32),
            "b": jnp.zeros((64,), jnp.float32),
        }
        return step, params, jnp.zeros((8, 64), jnp.float32)

    def test_planted_replicated_large_param_is_caught(self, eight_devices):
        """The headline defect: the plan shards w over tp but the call
        site replicates it — silent full replication is an ERROR."""
        mesh = _dp_tp_mesh(eight_devices)
        step, params, x = self._step()
        fn = jax.jit(step, in_shardings=(
            NamedSharding(mesh, P()), NamedSharding(mesh, P("dp", None))))
        report = analysis.check(
            fn, params, x,
            expect_sharding={
                "mesh": _DPTP, "rules": self.RULES, "min_bytes": 1 << 10,
            },
            rules=("sharding",),
        )
        assert report.rule_ids() == ["sharding-replicated"]
        assert not report.ok()
        assert "params/w" in report.findings[0].path

    def test_planted_wrong_axis_is_mismatch(self, eight_devices):
        mesh = _dp_tp_mesh(eight_devices)
        step, params, x = self._step()
        wrong = {"w": NamedSharding(mesh, P("tp", None)),  # transposed
                 "b": NamedSharding(mesh, P())}
        fn = jax.jit(step, in_shardings=(
            wrong, NamedSharding(mesh, P("dp", None))))
        report = analysis.check(
            fn, params, x,
            expect_sharding={
                "mesh": _DPTP, "rules": self.RULES, "min_bytes": 1 << 10,
            },
            rules=("sharding",),
        )
        assert report.rule_ids() == ["sharding-mismatch"]

    def test_clean_conformant_step(self, eight_devices):
        mesh = _dp_tp_mesh(eight_devices)
        step, params, x = self._step()
        good = {"w": NamedSharding(mesh, P(None, "tp")),
                "b": NamedSharding(mesh, P())}
        fn = jax.jit(step, in_shardings=(
            good, NamedSharding(mesh, P("dp", None))))
        report = analysis.check(
            fn, params, x,
            expect_sharding={
                "mesh": _DPTP, "rules": self.RULES, "min_bytes": 1 << 10,
            },
            rules=("sharding",),
        )
        assert report.findings == [], report.render()

    def test_single_device_compile_is_unverified_not_clean(self):
        """A plan naming a real mesh checked against a 1-partition
        compile must WARN, not pass — nobody proved anything."""
        step, params, x = self._step()
        report = analysis.check(
            jax.jit(step), params, x,
            expect_sharding={
                "mesh": _DPTP, "rules": self.RULES, "min_bytes": 1 << 10,
            },
            rules=("sharding",),
        )
        assert report.rule_ids() == ["sharding-unverified"]
        assert report.ok()  # warning severity: visible, not fatal
        assert not report.ok(fail_on="warning")


class TestReshardPlan:
    def test_planted_unplanned_weight_all_gather(self, eight_devices):
        """The signature of a spec that didn't survive propagation:
        a weight all-gather the plan does not predict."""
        mesh = _dp_tp_mesh(eight_devices)

        def bad(w, x):
            wfull = jax.lax.all_gather(w, "tp", axis=0, tiled=True)
            y = jnp.einsum("bk,kn->bn", x, wfull)
            return jax.lax.psum(y, "tp")

        fn = jax.jit(jax.shard_map(
            bad, mesh=mesh,
            in_specs=(P("tp", None), P(None, None)),
            out_specs=P(None, None), check_vma=False,
        ))
        plan = {"mesh": _DPTP, "collectives": [
            {"kind": "all-reduce", "axis": "tp", "dtypes": ["f32"]},
        ]}
        report = analysis.check(
            fn, jnp.zeros((64, 32)), jnp.zeros((8, 64)),
            expect_plan=plan, rules=("reshard",),
        )
        assert report.rule_ids() == ["reshard-unplanned"]
        f = report.findings[0]
        assert "all-gather" in f.path and "tp" in f.path

    def test_planted_wire_drift(self, eight_devices):
        """A plan promising an int8 wire must fail when the compiled
        payload is f32 — the quantization didn't apply."""
        mesh = _dp_tp_mesh(eight_devices)

        def step(w, x):
            return jax.lax.psum(jnp.einsum("bk,kn->bn", x, w), "tp")

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("tp", None), P(None, None)),
            out_specs=P(None, None), check_vma=False,
        ))
        plan = {"mesh": _DPTP, "collectives": [
            {"kind": "all-reduce", "axis": "tp", "dtypes": ["s8"]},
        ]}
        report = analysis.check(
            fn, jnp.zeros((64, 32)), jnp.zeros((8, 32)),
            expect_plan=plan, rules=("reshard",),
        )
        assert report.rule_ids() == ["reshard-plan"]

    def test_ddp_declared_plan_matches_compiled(self, eight_devices):
        """The engine's OWN declaration (collective_plan) verifies the
        engine's OWN compiled sync — the live 8-device check beside
        the existing collective one, for f32 and the int8 wire."""
        from apex_tpu import parallel_state as ps
        from apex_tpu.parallel import DistributedDataParallel

        mesh = ps.initialize_model_parallel()
        world = ps.get_data_parallel_world_size()
        params = {"w": jnp.zeros((64, 64), jnp.float32),
                  "b": jnp.zeros((8,), jnp.float32)}
        batch = (jnp.ones((16, 64)), jnp.ones((16, 64)))
        for wire in ("f32", "int8"):
            ddp = DistributedDataParallel(
                lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
                wire=wire,
            )
            fn = jax.jit(jax.shard_map(
                lambda p, b: ddp.value_and_grad(p, b), mesh=mesh,
                in_specs=(P(), P("dp")), out_specs=(P(), P()),
                # the quantized all-gather is replicated by construction
                # but typed varying
                check_vma=(wire == "f32"),
            ))
            plan = ddp.collective_plan(params, world)
            report = analysis.check(
                fn, params, batch, expect_plan=plan,
                rules=("reshard",), name=f"ddp/{wire}",
            )
            assert report.findings == [], (wire, report.render())
            if wire == "int8":
                kinds = {e["kind"] for e in plan["collectives"]}
                assert kinds == {"all-to-all", "all-gather", "all-reduce"}

    def test_zero_declared_plan_matches_compiled(self, eight_devices):
        """The ZeRO optimizer's own declaration verifies its own
        compiled step: int8 grad reduce-scatter (all-to-all on the
        wire), f32 param all-gather.  (A bf16 param_wire is exactly
        what the pass is FOR on the CPU backend: XLA legally hoists
        the decode before the gather there, doubling wire bytes —
        reshard-plan fires — so the clean pin uses wires that hold.)"""
        from apex_tpu import parallel_state as ps
        from apex_tpu.parallel import DistributedFusedAdam

        mesh = ps.initialize_model_parallel()
        world = ps.get_data_parallel_world_size()
        params = {"w": jnp.zeros((64, 64), jnp.float32),
                  "b": jnp.zeros((8,), jnp.float32)}
        batch = (jnp.ones((16, 64)), jnp.ones((16, 64)))
        tx = DistributedFusedAdam(wire="int8", param_wire="f32")
        state = tx.init(params, world)
        step = tx.make_train_step(
            lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2), mesh
        )
        plan = tx.collective_plan()
        report = analysis.check(
            step, params, state, batch, expect_plan=plan,
            rules=("reshard",), name="zero/int8",
        )
        assert report.findings == [], report.render()

    # the dp collectives XLA:TPU compiled the demo trainer's ZeRO update
    # to at dp=2 x tp=2 (v5e 2x2, PR 21): no reduce-scatter, no
    # all-gather — each is an all-reduce of the full 65,920-element
    # buffer, the first carrying the loss pmean along
    _TPU_ZERO_HLO = """
ENTRY %main {
  %g = f32[65920]{0} parameter(0)
  %l = f32[] parameter(1)
  %all-reduce.2 = (f32[65920]{0:T(1024)S(1)}, f32[]{:T(128)}) all-reduce(%g, %l), channel_id=2, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  %s = f32[65920]{0} get-tuple-element(%all-reduce.2), index=0
  ROOT %all-reduce.1 = f32[65920]{0:T(1024)S(1)} all-reduce(%s), channel_id=3, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%max
}
"""

    def _zero_plan(self, n=65920):
        from apex_tpu.parallel import comm

        return {"mesh": _DPTP, "collectives": comm.zero_plan(n, 2, "dp")}

    def test_zero_plan_accepts_the_all_reduce_form(self):
        report = analysis.lint_hlo(
            self._TPU_ZERO_HLO, expect_plan=self._zero_plan(),
            rules=("reshard",),
        )
        assert report.findings == [], report.render()

    def test_all_reduce_form_keeps_the_byte_bound(self):
        """The credit is the planned ops' own allowance: a buffer twice
        the planned size busts it, and a reduce-scatter that is simply
        gone (no all-reduce beyond the plan's scalars) is still a
        count finding."""
        report = analysis.lint_hlo(
            self._TPU_ZERO_HLO, expect_plan=self._zero_plan(n=65920 // 2),
            rules=("reshard",),
        )
        assert report.rule_ids() == ["reshard-plan"]
        assert "all-reduce@dp" in report.findings[0].path
        gone = analysis.lint_hlo(
            _AR_HLO.replace("f32[8,128]", "f32[8]").replace(
                "{{0,1}}", "{{0,2},{1,3}}"
            ),
            expect_plan=self._zero_plan(), rules=("reshard",),
        )
        assert sorted(f.path for f in gone.findings) == [
            "all-gather@dp", "reduce-scatter@dp",
        ]


class TestMemoryBudget:
    _HLO = """
HloModule jit_f, is_scheduled=true

ENTRY %main (p0: f32[256,64], p1: f32[64,64]) -> f32[256,64] {
  %p0 = f32[256,64]{1,0} parameter(0), metadata={op_name="state[\\'params\\'][\\'w\\']"}
  %p1 = f32[64,64]{1,0} parameter(1), metadata={op_name="state[\\'opt\\'].m[\\'w\\']"}
  %dot = f32[256,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %exp = f32[256,64]{1,0} exponential(f32[256,64]{1,0} %dot)
  ROOT %add = f32[256,64]{1,0} add(f32[256,64]{1,0} %exp, f32[256,64]{1,0} %p0)
}
"""

    def test_estimate_peak_on_fixture(self):
        """Hand-checkable live ranges: p1 dies feeding %dot, p0 lives
        to the ROOT (its last use), %dot dies feeding %exp — the peak
        is p0 + two activations at instruction 3/4."""
        big = 256 * 64 * 4  # p0 / dot / exp / add are 64 KiB each
        est = memory_lib.estimate_peak(self._HLO)
        assert est["peak_bytes"] == 3 * big
        cats = est["by_category"]
        assert cats["params"] == big          # p0, alive at the peak
        assert cats["activations"] == 2 * big
        assert "optimizer" not in cats        # p1 died at %dot
        names = [b["name"] for b in est["buffers"]]
        assert "p0" in names
        # the arg-path classifier puts optimizer state in its bucket
        assert memory_lib.categorize_buffer(
            "parameter", "state['opt'].m['w']"
        ) == "optimizer"
        assert memory_lib.categorize_buffer(
            "parameter", "kv_pages"
        ) == "kv_cache"

    def test_planted_budget_overflow_is_caught(self):
        report = analysis.lint_hlo(
            self._HLO, hbm_budget=100_000, rules=("memory",)
        )
        assert report.rule_ids() == ["memory-budget"]
        f = report.findings[0]
        assert "params:p0" in f.message  # top-buffer attribution
        clean = analysis.lint_hlo(
            self._HLO, hbm_budget=10 << 20, rules=("memory",)
        )
        assert clean.findings == []

    def test_live_budget_overflow_on_compiled_step(self):
        def step(x):
            return (x @ x.T).sum()

        report = analysis.check(
            step, jnp.zeros((128, 128), jnp.float32), hbm_budget=1024,
            rules=("memory",),
        )
        assert report.rule_ids() == ["memory-budget"]

    # -- the serving KV pool's one-buffer gate (memory-pool-copy) ---------

    _POOL = (3, 16, 2, 8, 128)  # (L, P, H/G, page, D*G)

    @staticmethod
    def _pool_xs_ys(pool, rows, page_ids, slots):
        """PLANTED: the donated pool scanned as xs/ys — each layer is
        sliced out, updated and restacked into another buffer."""
        def layer(x, xs):
            pages, r = xs
            pages = pages.at[page_ids, :, slots].set(r)
            return x + pages[page_ids].sum(), pages

        return jax.lax.scan(layer, jnp.float32(0), (pool, rows))

    @staticmethod
    def _pool_carried(pool, rows, page_ids, slots):
        """The serving form: the pool is the loop's carry, written
        page-granular at ``[layer, page_ids]`` (serve/cache.py)."""
        from apex_tpu.serve import cache as cache_lib

        def layer(carry, xs):
            x, pool = carry
            r, l = xs
            pool = cache_lib.append_rows(pool, l, page_ids, slots, r)
            return (x + pool[l, page_ids].sum(), pool), None

        (x, pool), _ = jax.lax.scan(
            layer, (jnp.float32(0), pool),
            (rows, jnp.arange(pool.shape[0])),
        )
        return x, pool

    def _pool_args(self):
        l, _p, r, _page, w = self._POOL
        return (
            jnp.zeros(self._POOL, jnp.float32),
            jnp.ones((l, 4, r, w), jnp.float32),
            jnp.asarray([1, 5, 0, 0], jnp.int32),
            jnp.asarray([0, 3, 0, 0], jnp.int32),
        )

    def test_planted_pool_scanned_as_xs_ys_is_caught(self):
        report = analysis.check(
            self._pool_xs_ys, *self._pool_args(), donate_argnums=(0,),
            expect_pool={"shapes": [self._POOL]}, rules=("memory",),
        )
        assert report.rule_ids() == ["memory-pool-copy"]
        assert report.errors(), report.render()
        assert "shaped like the KV pool" in report.findings[0].message

    def test_carried_pool_with_page_writes_is_clean(self):
        report = analysis.check(
            self._pool_carried, *self._pool_args(), donate_argnums=(0,),
            expect_pool={"shapes": [self._POOL]},
            rules=("memory", "donation"),
        )
        assert report.findings == [], report.render()
        # unarmed (no expect_pool) the planted program is quiet too
        quiet = analysis.check(
            self._pool_xs_ys, *self._pool_args(), donate_argnums=(0,),
            rules=("memory",),
        )
        assert quiet.findings == []

    def test_pool_rule_severity_and_transposed_copies(self):
        """A relayout may print the pool transposed; the intent's
        severity downgrades the finding (the int8 scale planes)."""
        hlo = """
HloModule m, is_scheduled=true

ENTRY %main (p0: bf16[3,16,2,8,128]) -> bf16[3,16,2,8,128] {
  %p0 = bf16[3,16,2,8,128]{4,3,2,1,0} parameter(0)
  %t = bf16[16,3,2,8,128]{4,3,2,1,0} transpose(%p0), dimensions={1,0,2,3,4}
  ROOT %c = bf16[3,16,2,8,128]{4,3,2,1,0} copy(%t)
}
"""
        want = {"shapes": [self._POOL], "severity": analysis.WARNING}
        report = analysis.lint_hlo(hlo, expect_pool=want, rules=("memory",))
        assert report.rule_ids() == ["memory-pool-copy"]
        assert report.errors() == []
        assert "2 instruction(s)" in report.findings[0].message

    def test_engine_programs_pass_the_pool_rule(self):
        """Every program the engine compiles (decode, prefill, chunked
        prefill, fork, speculative draft / verify / rollback) builds
        under ``verify=True``; off the TPU the rule is a WARNING, and
        only the CPU compiler's own copy insertion around the chunk
        program's read-then-write of the pool trips it."""
        from apex_tpu.models.gpt import GptConfig, GptModel
        from apex_tpu.observability.metrics import board
        from apex_tpu.serve import InferenceEngine, ServeConfig
        from apex_tpu.serve.spec import SpecConfig

        cfg = GptConfig(
            vocab_size=64, hidden_size=128, num_layers=2, num_heads=2,
            intermediate_size=64, max_seq_len=128, dtype=jnp.float32,
        )
        params = GptModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
        )
        eng = InferenceEngine(
            cfg, params,
            ServeConfig(page_size=8, num_pages=32, max_batch=2,
                        max_pages_per_seq=8, verify=True),
            spec=SpecConfig(draft_params=None, k=2),
        )
        assert eng.cache["k"].shape == (2, 32, 1, 8, 128)  # G = 2
        eng.build(buckets=(16,), chunked=True)
        flagged = {
            name for name, report in eng.reports.items()
            if report.by_rule("memory-pool-copy")
        }
        assert flagged <= {"chunk_prefill_16"}, flagged
        assert len(eng.reports) == 9
        for name in eng.reports:
            assert board.get(f"serve/hbm/{name}/temp_bytes") is not None

    def test_memory_budget_watchdog_rule(self):
        from apex_tpu.observability import MemoryBudgetRule
        from apex_tpu.observability.metrics import board

        board.clear()
        rule = MemoryBudgetRule(budget_bytes=1000)
        assert rule.evaluate(None, 0) == []  # no estimate published
        memory_lib.publish_peak(
            {"peak_bytes": 950, "by_category": {"params": 950}}
        )
        (warn,) = rule.evaluate(None, 1)
        assert warn.severity == "warn"
        memory_lib.publish_peak({"peak_bytes": 2000, "by_category": {}})
        (crit,) = rule.evaluate(None, 2)
        assert crit.severity == "critical"
        assert board.get("analysis/peak_hbm_bytes") == 2000
        with pytest.raises(ValueError):
            MemoryBudgetRule(budget_bytes=0)
        board.clear()


class TestCleanDpTpStep:
    def test_clean_dp_tp_step_proves_whole_plan(self, eight_devices):
        """The acceptance fixture: a dp=2 x tp=2 step with declared
        rule table, collective plan, and budget — every sharding/
        memory pass runs and the clean step yields ZERO findings."""
        mesh = _dp_tp_mesh(eight_devices)
        B, K, N = 8, 32, 16
        rules = [(r"\bw$", P("tp", None)), (r"\bx$", P("dp", "tp"))]

        def step(w, x):
            y = jax.lax.psum(jnp.einsum("bk,kn->bn", x, w), "tp")
            return jax.lax.pmean(jnp.mean(y * y), ("dp", "tp"))

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("tp", None), P("dp", "tp")),
            out_specs=P(), check_vma=False,
        ))
        plan = {"mesh": _DPTP, "collectives": [
            {"kind": "all-reduce", "axis": "tp", "count": 1,
             "bytes": [0, (B // 2) * N * 4 + 64], "dtypes": ["f32"]},
        ]}
        report = analysis.check(
            fn, jnp.zeros((K, N), jnp.float32),
            jnp.zeros((B, K), jnp.float32),
            expect_sharding={
                "mesh": _DPTP, "rules": rules, "min_bytes": 0,
            },
            expect_plan=plan,
            hbm_budget=10 << 20,
        )
        assert report.findings == [], report.render()
        for name in ("sharding", "reshard", "memory"):
            assert name in report.rules_run
            assert name in report.pass_timings


# ---------------------------------------------------------------------------
# report plumbing for the new passes: dedupe, timings, merge, sections
# ---------------------------------------------------------------------------


def test_publish_report_dedupes_same_rule_and_location():
    """Two passes emitting the same (rule, location) — e.g. the jaxpr
    and HLO substrates of one defect — must gauge ONE defect onto the
    board (the ISSUE 9 bugfix), while the report keeps both raw
    findings for rendering."""
    from apex_tpu.observability.metrics import board

    board.clear()
    dup1 = analysis.make_finding("retrace", path="site_a", message="m1")
    dup2 = analysis.make_finding("retrace", path="site_a", message="m2")
    other = analysis.make_finding("retrace", path="site_b", message="m3")
    report = analysis.Report([dup1, dup2, other], target="dedupe")
    report.pass_timings["retrace"] = 1.25
    analysis.publish_report(report)
    snap = board.snapshot()
    assert snap["analysis/rule/retrace"] == 2  # a+b, not 3
    assert snap["analysis/errors"] == 2
    assert snap["analysis/pass_ms/retrace"] == 1.25
    assert len(report.findings) == 3  # raw findings untouched
    board.clear()


def test_pass_timings_cover_rules_run_and_survive_to_json():
    report = analysis.check(lambda x: x * 2.0, jnp.zeros((4,)))
    assert set(report.pass_timings) == set(report.rules_run)
    assert all(ms >= 0.0 for ms in report.pass_timings.values())
    blob = json.loads(report.to_json_line())
    assert set(blob["pass_timings"]) == set(report.rules_run)


def test_report_merge_sums_timings_and_unions_rules():
    a = analysis.Report(target="a", rules_run=("transfer",))
    a.pass_timings = {"transfer": 1.0}
    b = analysis.Report(
        [analysis.make_finding("retrace", path="p", message="m")],
        target="b", rules_run=("transfer", "memory"),
    )
    b.pass_timings = {"transfer": 2.0, "memory": 0.5}
    a.merge(b)
    assert a.pass_timings == {"transfer": 3.0, "memory": 0.5}
    assert a.rules_run == ("transfer", "memory")
    assert len(a.findings) == 1


def test_attach_shard_sections_rides_to_json():
    hlo = TestMemoryBudget._HLO
    report = analysis.lint_hlo(hlo, rules=("memory",), name="fixture")
    analysis.attach_shard_sections(
        report, [("fixture", hlo)], publish=True
    )
    blob = report.to_json()
    assert blob["peak_hbm_bytes"] > 0
    assert blob["peak_hbm_by_program"] == {
        "fixture": blob["peak_hbm_bytes"]}
    assert {r["name"] for r in blob["shard_plan"]} == {
        "state/params/w", "state/opt/m/w"}
    from apex_tpu.observability.metrics import board

    assert board.get("analysis/peak_hbm_bytes") == blob["peak_hbm_bytes"]
    board.clear()


# ---------------------------------------------------------------------------
# repo_lint source rules (the satellite): in_shardings=None, missing
# with_sharding_constraint
# ---------------------------------------------------------------------------


def test_repo_lint_sharding_source_rules():
    from tools import repo_lint

    implicit = [
        "def build(step):",
        "    return pjit(step, in_shardings=None, out_shardings=None)",
    ]
    got = repo_lint._sharding_violations("x/m.py", implicit, jitted=True)
    assert len(got) == 1 and got[0][1] == 2
    assert "replicated" in got[0][3]

    unpinned = [
        "y = jnp.einsum('bk,kn->bn', x, w)",
        "fn = shard_map(step, mesh=mesh, in_specs=specs)",
    ]
    got = repo_lint._sharding_violations("x/m.py", unpinned, jitted=True)
    assert len(got) == 1 and "with_sharding_constraint" in got[0][4]

    # pinning ANY intermediate waives the call-site rule
    pinned = unpinned + [
        "y = jax.lax.with_sharding_constraint(y, spec)",
    ]
    assert repo_lint._sharding_violations("x/m.py", pinned, True) == []
    # host-side files are out of scope
    assert repo_lint._sharding_violations(
        "x/m.py", implicit + unpinned, jitted=False
    ) == []
    # the waiver comment works like every other repo_lint rule
    waived = [
        "fn = pjit(step, in_shardings=None)  # repo-lint: allow tests",
    ]
    assert repo_lint._sharding_violations("x/m.py", waived, True) == []


def test_bench_shard_lint_line_passes_schema():
    """The `graph_lint_shard_errors` line bench.py --lint emits rides
    the standard bench-record contract tools/bench_diff.py enforces."""
    from tools import bench_diff

    rec = {
        "metric": "graph_lint_shard_errors",
        "value": 0.0,
        "unit": "sharding/reshard/memory ERROR findings (bert_lamb "
                "step; peak_hbm=123.4MiB; docs/analysis.md)",
        "vs_baseline": None,
    }
    assert bench_diff.check_schema([rec]) == []

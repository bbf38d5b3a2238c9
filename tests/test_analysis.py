"""vitax.analysis: parser units, rule positive/negative cases, AST lint.

Strategy: hand-written HLO/MLIR string fixtures drive the parser units and
every rule's NEGATIVE case (deliberately broken programs — a use-site gather,
an f32 gather under the bf16 policy, an outfeed in the step, a replicated
large param), so each rule provably FAILS on the violation it polices. The
POSITIVE cases run the real rules over real lowered programs (session-scoped:
one overlap train arm, one donation-off arm, one warmed serve engine), which
doubles as the end-to-end check that HEAD itself is clean.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from vitax.analysis import ast_lint, hlo, rules
from vitax.analysis.rules import (
    COLLECTIVE_DTYPE,
    DONATION_HONORED,
    FUSED_DEQUANT,
    FUSED_OPTIMIZER,
    GATHER_OVERLAP,
    NO_HOST_TRANSFER,
    NO_REPLICATED_LARGE,
    QUANT_WEIGHTS_RESIDENT,
    SERVE_NO_RECOMPILE,
    Program,
    arm_config,
    build_serve_program,
    build_train_program,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- HLO fixtures ------------------------------------------------------------

# A minimal partitioned-style module: a while loop whose body issues one
# all-gather consumed by a dot before the carry (a USE-SITE gather — the
# serial ZeRO-3 schedule).
HLO_USE_SITE = textwrap.dedent("""\
    HloModule jit_train_step, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }

    body.1 {
      p.1 = (f32[8,8], f32[8,8]) parameter(0)
      gte.0 = f32[8,8] get-tuple-element(p.1), index=0
      gte.1 = f32[8,8] get-tuple-element(p.1), index=1
      ag.1 = f32[8,8] all-gather(gte.0), dimensions={0}
      dot.1 = f32[8,8] dot(ag.1, gte.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT tuple.1 = (f32[8,8], f32[8,8]) tuple(dot.1, gte.1)
    }

    cond.1 {
      cp.1 = (f32[8,8], f32[8,8]) parameter(0)
      ROOT lt.1 = pred[] constant(false)
    }

    ENTRY main.1 {
      param.0 = f32[8,8] parameter(0)
      param.1 = f32[8,8] parameter(1)
      t.0 = (f32[8,8], f32[8,8]) tuple(param.0, param.1)
      w.1 = (f32[8,8], f32[8,8]) while(t.0), condition=cond.1, body=body.1
      ROOT out.0 = f32[8,8] get-tuple-element(w.1), index=0
    }
    """)

# Same loop but the gather's result rides the carry to ROOT through nothing
# but plumbing — the prefetch-slot schedule.
HLO_PREFETCH = HLO_USE_SITE.replace(
    "ROOT tuple.1 = (f32[8,8], f32[8,8]) tuple(dot.1, gte.1)",
    "cp2.1 = f32[8,8] copy(ag.1)\n"
    "  ROOT tuple.1 = (f32[8,8], f32[8,8]) tuple(dot.1, cp2.1)")

HLO_WITH_OUTFEED = HLO_USE_SITE.replace(
    "ROOT out.0 = f32[8,8] get-tuple-element(w.1), index=0",
    "tok.0 = token[] after-all()\n"
    "  of.1 = token[] outfeed(param.0, tok.0), outfeed_config=\"x\"\n"
    "  cc.1 = () custom-call(param.1), custom_call_target=\"xla_python_cpu_callback\"\n"
    "  ROOT out.0 = f32[8,8] get-tuple-element(w.1), index=0")


def mk_mlir(args):
    """StableHLO @main skeleton from [(type, attr_dict_text or None)]."""
    rendered = ", ".join(
        f"%arg{i}: {ty}" + (f" {{{attrs}}}" if attrs else "")
        for i, (ty, attrs) in enumerate(args))
    return textwrap.dedent(f"""\
        module @jit_train_step attributes {{mhlo.num_partitions = 8 : i32}} {{
          func.func public @main({rendered}) -> (tensor<f32>) {{
            %0 = stablehlo.constant dense<0.0> : tensor<f32>
            return %0 : tensor<f32>
          }}
        }}
        """)


SHARDED = 'mhlo.sharding = "{devices=[8,1]<=[8]}"'
REPLICATED = 'mhlo.sharding = "{replicated}"'


# --- parser units ------------------------------------------------------------


def test_collect_collectives_and_bytes():
    rows = hlo.collect_collectives(
        "  a = bf16[2,32]{1,0} all-gather(x), dims={0}\n"
        "  b = bf16[2,32]{1,0} all-gather(y), dims={0}\n"
        "  c = f32[16]{0} reduce-scatter(z), dims={0}\n"
        "  d = f32[4,4]{1,0} all-reduce-start(w), to_apply=add\n")
    by_op = {r["op"]: r for r in rows}
    assert by_op["all-gather"]["count"] == 2
    assert by_op["all-gather"]["dtype"] == "bf16"
    assert by_op["all-gather"]["bytes"] == 2 * 64 * 2
    assert by_op["reduce-scatter"]["bytes"] == 16 * 4
    assert "all-reduce" in by_op  # -start folded into the base op
    assert hlo.gather_bytes(rows) == 256
    assert hlo.gather_bytes(rows, dtype="f32") == 0
    totals = hlo.summarize(rows)
    assert totals["all-gather"]["by_dtype"]["bf16"]["count"] == 2


def test_split_computations_and_inventory():
    comps = hlo.split_computations(HLO_USE_SITE)
    assert set(comps) == {"body.1", "cond.1", "main.1"}
    assert len(comps["body.1"]) == 6
    inv = hlo.while_body_op_inventory(HLO_USE_SITE)
    assert inv["body.1"]["all-gather"] == 1
    assert inv["body.1"]["dot"] == 1


def test_overlap_verdict_use_site_vs_prefetch():
    use = hlo.overlap_verdict(HLO_USE_SITE)
    assert use["per_iteration_gather_count"] == {"body.1": 1}
    assert use["prefetch_slot_gathers"] == 0
    pre = hlo.overlap_verdict(HLO_PREFETCH)
    assert pre["per_iteration_gather_count"] == {"body.1": 1}
    assert pre["prefetch_slot_gathers"] == 1


def test_input_output_aliases_header():
    aliases = hlo.input_output_aliases(HLO_USE_SITE)
    assert [(a["output_index"], a["parameter"]) for a in aliases] == \
        [((0,), 0), ((1,), 1)]
    assert hlo.input_output_aliases("HloModule bare\n") == []


def test_host_transfer_ops():
    assert hlo.host_transfer_ops(HLO_USE_SITE) == []
    ops = hlo.host_transfer_ops(HLO_WITH_OUTFEED)
    assert [o["op"] for o in ops] == ["outfeed", "custom-call"]
    assert ops[1]["detail"] == "xla_python_cpu_callback"
    mops = hlo.mlir_host_transfer_ops(
        '    stablehlo.custom_call @xla_python_cpu_callback(%1) : x\n')
    assert mops and mops[0]["detail"] == "xla_python_cpu_callback"


def test_mlir_main_args_table():
    text = mk_mlir([
        ("tensor<64x64xf32>", SHARDED + ", tf.aliasing_output = 0 : i32"),
        ("tensor<8xf32>", REPLICATED + ", tf.aliasing_output = 1 : i32"),
        ("tensor<64x16x16x3xui8>", None),
    ])
    args = hlo.mlir_main_args(text)
    assert [a["index"] for a in args] == [0, 1, 2]
    assert args[0]["bytes"] == 64 * 64 * 4
    assert args[0]["donated_to"] == 0
    assert not hlo.sharding_is_replicated(args[0]["sharding"])
    assert hlo.sharding_is_replicated(args[1]["sharding"])
    assert args[2]["donated_to"] is None
    assert args[2]["sharding"] is None
    assert hlo.sharding_is_replicated(args[2]["sharding"])  # unannotated


def test_sharding_is_replicated_tiled_forms():
    assert hlo.sharding_is_replicated(
        "{devices=[1,1,8]<=[8] last_tile_dim_replicate}")
    assert not hlo.sharding_is_replicated("{devices=[8,1]<=[8]}")


# --- real lowered programs (session-scoped: ~10s each) -----------------------


@pytest.fixture(scope="session")
def overlap_program(devices8):
    return build_train_program(
        arm_config("zero3_overlap"), arm="zero3_overlap")


@pytest.fixture(scope="session")
def no_donate_program(devices8):
    return build_train_program(
        arm_config("zero3"), arm="zero3_nodonate", donate=False)


@pytest.fixture(scope="session")
def serve_program(devices8):
    return build_serve_program(arm_config("serve"))


@pytest.fixture(scope="session")
def serve_quant_program(devices8):
    return build_serve_program(arm_config("serve_quant"), arm="serve_quant")


# --- per-rule positive + negative cases --------------------------------------


def test_r001_host_transfer_positive(overlap_program):
    assert NO_HOST_TRANSFER.check(
        overlap_program, overlap_program.config) == []


def test_r001_host_transfer_negative(overlap_program):
    broken = Program(kind="train", arm="x", config=overlap_program.config,
                     partitioned_hlo=HLO_WITH_OUTFEED)
    findings = NO_HOST_TRANSFER.check(broken, broken.config)
    assert len(findings) == 2
    assert all(f.rule == "VTX-R001" and f.severity == "ERROR"
               for f in findings)


def test_r002_donation_positive(overlap_program):
    assert overlap_program.n_state_leaves > 0
    assert DONATION_HONORED.check(
        overlap_program, overlap_program.config) == []


def test_r002_donation_negative_donate_off(no_donate_program):
    findings = DONATION_HONORED.check(
        no_donate_program, no_donate_program.config)
    assert findings, "donation disabled must trip VTX-R002"
    assert findings[0].rule == "VTX-R002"
    assert findings[0].details["donated"] == 0


def test_r003_collective_dtype_positive(overlap_program):
    assert overlap_program.config.comm_cast_active
    assert COLLECTIVE_DTYPE.check(
        overlap_program, overlap_program.config) == []


def test_r003_collective_dtype_negative():
    cfg = arm_config("zero3")  # bf16 policy active, embed_dim=32
    assert COLLECTIVE_DTYPE.applies_to(cfg)
    d = cfg.embed_dim
    broken = Program(
        kind="train", arm="x", config=cfg,
        partitioned_hlo=f"  ag = f32[{d},{d}]{{1,0}} all-gather(p), dims={{0}}\n")
    findings = COLLECTIVE_DTYPE.check(broken, cfg)
    assert len(findings) == 1 and findings[0].rule == "VTX-R003"
    # sub-threshold f32 gathers (bias-sized) stay legal
    small = Program(
        kind="train", arm="x", config=cfg,
        partitioned_hlo=f"  ag = f32[{d}]{{0}} all-gather(p), dims={{0}}\n")
    assert COLLECTIVE_DTYPE.check(small, cfg) == []


def test_r003_not_applicable_without_policy():
    assert not COLLECTIVE_DTYPE.applies_to(arm_config("dp"))


def test_r004_gather_overlap_positive(overlap_program):
    assert GATHER_OVERLAP.applicable(overlap_program)
    assert GATHER_OVERLAP.check(
        overlap_program, overlap_program.config) == []


def test_r004_gather_overlap_negative():
    cfg = arm_config("zero3_overlap")
    broken = Program(kind="train", arm="x", config=cfg,
                     partitioned_hlo=HLO_USE_SITE,
                     mesh_shape={"dp": 1, "fsdp": 8})
    findings = GATHER_OVERLAP.check(broken, cfg)
    assert len(findings) == 1 and findings[0].rule == "VTX-R004"
    assert "use-site" in findings[0].message
    ok = Program(kind="train", arm="x", config=cfg,
                 partitioned_hlo=HLO_PREFETCH,
                 mesh_shape={"dp": 1, "fsdp": 8})
    assert GATHER_OVERLAP.check(ok, cfg) == []


def test_r005_replicated_large_positive(overlap_program):
    assert NO_REPLICATED_LARGE.applicable(overlap_program)
    assert NO_REPLICATED_LARGE.check(
        overlap_program, overlap_program.config) == []


def test_r005_replicated_large_negative():
    cfg = arm_config("zero3")
    d = cfg.embed_dim  # threshold is d*d*4 bytes; a d*d f32 donated arg tips it
    broken = Program(
        kind="train", arm="x", config=cfg,
        mlir=mk_mlir([
            (f"tensor<{d}x{d}xf32>",
             REPLICATED + ", tf.aliasing_output = 0 : i32"),
            (f"tensor<{d}x{d}xf32>",
             SHARDED + ", tf.aliasing_output = 1 : i32"),
        ]),
        mesh_shape={"dp": 1, "fsdp": 8})
    findings = NO_REPLICATED_LARGE.check(broken, cfg)
    assert len(findings) == 1 and findings[0].rule == "VTX-R005"
    assert findings[0].details["arg"]["index"] == 0


def test_r006_serve_positive(serve_program):
    assert SERVE_NO_RECOMPILE.check(
        serve_program, serve_program.config) == []


def test_r006_serve_negative(serve_program):
    class LeakyEngine:
        """compile_count drifted past the bucket set: recompiles happened."""
        buckets = (1,)
        compile_count = 3
        params = compute_params = None
        _compiled = {1: lambda *a, **k: None}  # accepts anything: also bad
        _batch_shardings = {1: None}

        def predict(self, images):
            return None, None

    broken = Program(kind="serve", arm="serve", config=serve_program.config,
                     engine=LeakyEngine())
    findings = SERVE_NO_RECOMPILE.check(broken, broken.config)
    codes = [f.message for f in findings]
    assert any("compile_count 3 != bucket count 1" in m for m in codes)
    assert any("accepted an unseen input shape" in m for m in codes)


def test_r007_quant_resident_positive(serve_quant_program):
    prog = serve_quant_program
    assert QUANT_WEIGHTS_RESIDENT.applicable(prog)
    assert prog.engine.scales, "serve_quant arm must carry quant scales"
    assert QUANT_WEIGHTS_RESIDENT.check(prog, prog.config) == []
    # R006 reads the quantized engine too: the AOT contract is dtype-blind
    assert SERVE_NO_RECOMPILE.check(prog, prog.config) == []


def test_r007_not_applicable_without_quant(serve_program):
    assert not QUANT_WEIGHTS_RESIDENT.applicable(serve_program)


def test_r007_quant_resident_negative():
    import numpy as np
    cfg = arm_config("serve_quant")
    d = cfg.embed_dim

    class DequantedEngine:
        """The violation R007 exists for: the scaled leaf was dequantized at
        load (f32 on device) and the lowered program takes a block-sized f32
        weight argument instead of the int8 one."""
        buckets = (1, 2, 4)
        scales = {"params/blocks/mlp/fc1/kernel": np.ones((1, 1, d * 4),
                                                          np.float32)}
        params = {"params": {"blocks": {"mlp": {"fc1": {
            "kernel": np.zeros((2, d, d * 4), np.float32)}}}}}

        def lower_bucket_mlir(self, bucket):
            return mk_mlir([(f"tensor<2x{d}x{d * 4}xf32>", SHARDED),
                            (f"tensor<4x{cfg.image_size}x{cfg.image_size}"
                             f"x3xui8>", None)])

    broken = Program(kind="serve", arm="serve_quant", config=cfg,
                     engine=DequantedEngine())
    findings = QUANT_WEIGHTS_RESIDENT.check(broken, cfg)
    msgs = [f.message for f in findings]
    assert all(f.rule == "VTX-R007" and f.severity == "ERROR"
               for f in findings)
    assert any("resident as float32, not int8" in m for m in msgs)
    assert any("0 i8 arguments for 1 scaled leaves" in m for m in msgs)
    assert any("block-sized floating argument" in m for m in msgs)

    class UnquantizedEngine(DequantedEngine):
        scales = {}

    unq = Program(kind="serve", arm="serve_quant", config=cfg,
                  engine=UnquantizedEngine())
    findings = QUANT_WEIGHTS_RESIDENT.check(unq, cfg)
    assert len(findings) == 1
    assert "no quant scales" in findings[0].message


# --- tier 2: fp8 arm + fused dequant-matmul (VTX-R009) -----------------------


@pytest.fixture(scope="session")
def serve_fp8_program(devices8):
    return build_serve_program(arm_config("serve_fp8"), arm="serve_fp8")


@pytest.fixture(scope="session")
def serve_actquant_program(devices8):
    return build_serve_program(
        arm_config("serve_actquant"), arm="serve_actquant")


def test_r007_fp8_positive(serve_fp8_program):
    """R007 is dtype-keyed: the fp8 arm passes the same residency/arg checks
    against float8_e4m3 leaves and f8E4M3 program arguments."""
    import ml_dtypes
    import numpy as np
    prog = serve_fp8_program
    assert prog.engine.weights_dtype == "float8_e4m3"
    assert QUANT_WEIGHTS_RESIDENT.applicable(prog)
    assert QUANT_WEIGHTS_RESIDENT.check(prog, prog.config) == []
    assert SERVE_NO_RECOMPILE.check(prog, prog.config) == []
    fp8 = np.dtype(ml_dtypes.float8_e4m3)
    import jax
    fp8_leaves = [v for v in jax.tree.leaves(prog.engine.params)
                  if np.dtype(v.dtype) == fp8]
    assert len(fp8_leaves) == len(prog.engine.scales)


def test_r007_fp8_negative_int8_leaves():
    """Wrong quant dtype on device (int8 leaves under an fp8 config) trips
    both the residency check and the program-argument count."""
    import numpy as np
    cfg = arm_config("serve_fp8")
    d = cfg.embed_dim

    class WrongDtypeEngine:
        buckets = (1, 2, 4)
        scales = {"params/blocks/mlp/fc1/kernel": np.ones((1, 1, d * 4),
                                                          np.float32)}
        params = {"params": {"blocks": {"mlp": {"fc1": {
            "kernel": np.zeros((2, d, d * 4), np.int8)}}}}}

        def lower_bucket_mlir(self, bucket):
            return mk_mlir([(f"tensor<2x{d}x{d * 4}xi8>", SHARDED)])

    broken = Program(kind="serve", arm="serve_fp8", config=cfg,
                     engine=WrongDtypeEngine())
    findings = QUANT_WEIGHTS_RESIDENT.check(broken, cfg)
    msgs = [f.message for f in findings]
    assert any("not float8_e4m3" in m for m in msgs)
    assert any("0 f8E4M3 arguments for 1 scaled leaves" in m for m in msgs)


def test_r009_fused_positive(serve_actquant_program):
    from vitax.ops.dequant_matmul import DEQUANT_KERNEL_NAME
    prog = serve_actquant_program
    assert prog.engine.fused_dequant is True
    assert FUSED_DEQUANT.applicable(prog)
    jaxpr = prog.engine.trace_bucket_jaxpr(prog.engine.buckets[-1])
    assert jaxpr.count(DEQUANT_KERNEL_NAME) >= 1
    assert FUSED_DEQUANT.check(prog, prog.config) == []


def test_r009_negative_unfused_build(serve_quant_program,
                                     serve_actquant_program):
    """Teeth check: the SAME rule over a deliberately unfused serve engine
    (the weight-only dequantize_tree program attached to a fused-on config)
    must fire BOTH checks — no kernel launch, and the weight-sized i8->f32
    converts at the top level of the traced program."""
    cfg_on = serve_actquant_program.config
    broken = Program(kind="serve", arm="serve_actquant", config=cfg_on,
                     engine=serve_quant_program.engine)
    findings = FUSED_DEQUANT.check(broken, cfg_on)
    msgs = [f.message for f in findings]
    assert all(f.rule == "VTX-R009" and f.severity == "ERROR"
               for f in findings)
    assert any("no dequant_matmul_kernel" in m for m in msgs)
    assert any("weight-sized dequant outside the fused kernel" in m
               for m in msgs), msgs


def test_r009_not_applicable_without_fused():
    # weight-only int8 (fused auto resolves off on CPU) and the fp8 arm:
    # the rule must not bind, keeping the serve rules_ran pins stable
    assert not FUSED_DEQUANT.applies_to(arm_config("serve_quant"))
    assert not FUSED_DEQUANT.applies_to(arm_config("serve_fp8"))
    assert FUSED_DEQUANT.applies_to(arm_config("serve_actquant"))


def test_tier2_serve_rules_ran_pins(serve_fp8_program,
                                    serve_actquant_program):
    ran8, findings8 = rules.run_rules(serve_fp8_program)
    assert ran8 == ["VTX-R006", "VTX-R007"] and findings8 == []
    ran_a, findings_a = rules.run_rules(serve_actquant_program)
    assert ran_a == ["VTX-R006", "VTX-R007", "VTX-R009"]
    assert findings_a == []


def test_jaxpr_quant_dequant_converts_unit():
    """Parser unit for the R009 helper: sub-jaxpr bodies are stripped (no
    var shadowing), only i8/f8-sourced converts count (u8 images never
    do), and the exempt-shape and min-elems filters apply."""
    text = textwrap.dedent("""\
        { lambda ; a:i8[2,32,96] b:u8[4,16,16,3] c:f8_e4m3[32,4] d:i8[8,8,3,32]
            e:i8[2,2]. let
            f:f32[2,32,96] = convert_element_type[new_dtype=float32] a
            g:f32[4,16,16,3] = convert_element_type[new_dtype=float32] b
            h:f32[32,4] = convert_element_type[new_dtype=float32] c
            i:f32[8,8,3,32] = convert_element_type[new_dtype=float32] d
            j:f32[2,2] = convert_element_type[new_dtype=float32] e
            k:f32[2,32,96] = pjit[
              jaxpr={ lambda ; a:f32[2,32,96]. let
                  b:f32[2,32,96] = mul a 2.0
                in (b,) }
            ] f
          in (k,) }
        """)
    rows = hlo.jaxpr_quant_dequant_converts(
        text, min_elems=128, exempt_shapes=((8, 8, 3, 32),))
    # a (i8, 6144 elems) and c (f8, 128 elems) fire; b is u8 (image), d is
    # the exempt conv shape, e is sub-threshold
    assert [(r["src_dtype"], tuple(r["shape"])) for r in rows] == [
        ("i8", (2, 32, 96)), ("f8_e4m3", (32, 4))]


@pytest.fixture(scope="session")
def fused_program(devices8):
    return build_train_program(arm_config("fused"), arm="fused")


def test_r008_fused_positive(fused_program):
    from vitax.ops.fused_optimizer import FUSED_KERNEL_NAME
    assert fused_program.jaxpr, "fused arm must capture the jaxpr artifact"
    assert fused_program.jaxpr.count(FUSED_KERNEL_NAME) >= 1
    assert FUSED_OPTIMIZER.check(fused_program, fused_program.config) == []


def test_r008_fused_negative_unfused_build(fused_program):
    """Teeth check: the SAME rule over a deliberately unfused build (the
    optax-chain jaxpr attached to a fused-on config) must fire BOTH checks —
    no kernel launch, and the param-sized post-clip temporary chain."""
    cfg_on = fused_program.config
    unfused_jaxpr = hlo.train_step_jaxpr(arm_config("zero3"))
    broken = Program(kind="train", arm="fused", config=cfg_on,
                     jaxpr=unfused_jaxpr)
    findings = FUSED_OPTIMIZER.check(broken, cfg_on)
    msgs = [f.message for f in findings]
    assert all(f.rule == "VTX-R008" and f.severity == "ERROR"
               for f in findings)
    assert any("no fused_adamw_kernel" in m for m in msgs)
    assert any("param-sized f32 sqrt" in m for m in msgs), msgs


def test_r008_missing_artifact_is_a_finding(fused_program):
    empty = Program(kind="train", arm="fused", config=fused_program.config)
    findings = FUSED_OPTIMIZER.check(empty, empty.config)
    assert len(findings) == 1
    assert "without a traced-jaxpr artifact" in findings[0].message


def test_r008_not_applicable_on_cpu_auto():
    # CPU default (auto -> interpret -> optax chain): the rule must not bind,
    # keeping every existing arm's rules_ran pin valid
    assert not FUSED_OPTIMIZER.applies_to(arm_config("zero3"))
    assert FUSED_OPTIMIZER.applies_to(arm_config("fused"))


def test_r008_rules_ran_pin(fused_program):
    ran, findings = rules.run_rules(fused_program)
    assert ran == ["VTX-R001", "VTX-R002", "VTX-R003", "VTX-R005",
                   "VTX-R008"]
    assert findings == []


def test_run_rules_dispatch(overlap_program, serve_program,
                            serve_quant_program):
    ran, findings = rules.run_rules(overlap_program)
    assert ran == ["VTX-R001", "VTX-R002", "VTX-R003", "VTX-R004", "VTX-R005"]
    assert findings == []
    ran_s, findings_s = rules.run_rules(serve_program)
    assert ran_s == ["VTX-R006"] and findings_s == []
    ran_q, findings_q = rules.run_rules(serve_quant_program)
    assert ran_q == ["VTX-R006", "VTX-R007"] and findings_q == []


def test_comm_audit_reexports():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import comm_audit
    for name in ("collect_collectives", "summarize", "gather_bytes",
                 "overlap_verdict", "partitioned_hlo_text", "audit_config",
                 "format_report", "main"):
        assert callable(getattr(comm_audit, name)), name
    assert comm_audit.collect_collectives is hlo.collect_collectives


# --- check_invariants CLI (subprocess: one arm, ~20s) ------------------------


def test_check_invariants_json_schema(devices8):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_invariants.py"),
         "--arms", "zero3", "--json"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["schema"] == 1
    assert set(doc) == {"schema", "arms", "findings", "errors",
                        "concurrency", "ok"}
    assert doc["ok"] is True and doc["errors"] == {}
    assert doc["concurrency"]["ok"] is True
    assert doc["concurrency"]["findings"] == []
    arm = doc["arms"]["zero3"]
    assert set(arm) == {"ok", "rules_ran", "findings"}
    assert arm["rules_ran"] == ["VTX-R001", "VTX-R002", "VTX-R003", "VTX-R005"]
    assert arm["findings"] == []


def test_check_invariants_serve_quant_arm(devices8):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_invariants.py"),
         "--arms", "serve_quant", "--json"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True and doc["errors"] == {}
    arm = doc["arms"]["serve_quant"]
    assert set(arm) == {"ok", "rules_ran", "findings"}
    assert arm["rules_ran"] == ["VTX-R006", "VTX-R007"]
    assert arm["findings"] == []


def test_check_invariants_tier2_serve_arms(devices8):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_invariants.py"),
         "--arms", "serve_fp8", "serve_actquant", "--json"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True and doc["errors"] == {}
    arm8 = doc["arms"]["serve_fp8"]
    assert arm8["rules_ran"] == ["VTX-R006", "VTX-R007"]
    assert arm8["findings"] == []
    arm_a = doc["arms"]["serve_actquant"]
    assert arm_a["rules_ran"] == ["VTX-R006", "VTX-R007", "VTX-R009"]
    assert arm_a["findings"] == []


def test_check_invariants_fused_arm(devices8):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_invariants.py"),
         "--arms", "fused", "--json"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True and doc["errors"] == {}
    arm = doc["arms"]["fused"]
    assert set(arm) == {"ok", "rules_ran", "findings"}
    assert arm["rules_ran"] == ["VTX-R001", "VTX-R002", "VTX-R003",
                                "VTX-R005", "VTX-R008"]
    assert arm["findings"] == []


# --- AST lint ----------------------------------------------------------------


def _codes(findings):
    return sorted(f.code for f in findings)


def test_lint_device_get_in_traced_module():
    src = "import jax\ndef f(x):\n    return jax.device_get(x)\n"
    assert _codes(ast_lint.lint_source(src, "vitax/models/vit.py")) == ["VTX101"]
    # same construct outside the traced set is fine
    assert ast_lint.lint_source(src, "vitax/telemetry/record.py") == []


def test_lint_block_until_ready_and_float_on_traced():
    src = ("import jax, jax.numpy as jnp\n"
           "def f(x):\n"
           "    y = jnp.sum(x).block_until_ready()\n"
           "    return float(jnp.mean(y))\n")
    assert _codes(ast_lint.lint_source(src, "vitax/train/step.py")) == \
        ["VTX101", "VTX102"]


def test_lint_item_on_traced():
    src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.max(x).item()\n"
    assert _codes(ast_lint.lint_source(src, "vitax/ops/attention.py")) == \
        ["VTX102"]
    # .item() on a non-jax object is not flagged
    src2 = "def f(d):\n    return d.item()\n"
    assert ast_lint.lint_source(src2, "vitax/ops/attention.py") == []


def test_lint_unfenced_timing():
    src = ("import time\n"
           "def loop(step_fn, batch):\n"
           "    t0 = time.time()\n"
           "    out = step_fn(batch)\n"
           "    dt = time.time() - t0\n"
           "    return out, dt\n")
    assert _codes(ast_lint.lint_source(src, "vitax/train/loop.py")) == ["VTX103"]
    fenced = src.replace("    dt = time.time() - t0\n",
                         "    jax.block_until_ready(out)\n"
                         "    dt = time.time() - t0\n")
    assert ast_lint.lint_source(fenced, "vitax/train/loop.py") == []


def test_lint_argless_jax_devices():
    src = "import jax\ndef f():\n    return jax.devices()[0]\n"
    assert _codes(ast_lint.lint_source(src, "vitax/serve/server.py")) == \
        ["VTX104"]
    ok = "import jax\ndef f():\n    return jax.devices('cpu')[0]\n"
    assert ast_lint.lint_source(ok, "vitax/serve/server.py") == []


def test_lint_mutable_default():
    src = "def f(xs=[], m={}):\n    return xs, m\n"
    assert _codes(ast_lint.lint_source(src, "vitax/data/loader.py")) == \
        ["VTX105", "VTX105"]


def test_lint_suppression_with_reason():
    src = ("import jax\n"
           "def f():\n"
           "    return jax.devices()[0]  "
           "# vtx: ignore[VTX104] test needs the live device list\n")
    assert ast_lint.lint_source(src, "vitax/serve/server.py") == []


def test_lint_bare_suppression_is_error():
    src = ("import jax\n"
           "def f():\n"
           "    return jax.devices()[0]  # vtx: ignore[VTX104]\n")
    codes = _codes(ast_lint.lint_source(src, "vitax/serve/server.py"))
    assert "VTX100" in codes  # bare suppression flagged
    assert "VTX104" in codes  # and it does NOT suppress


def test_lint_repo_is_clean():
    findings = ast_lint.lint_paths([os.path.join(REPO, "vitax")])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_lint_cli(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    assert ast_lint.main([str(bad)]) == 1
    assert ast_lint.main([str(bad), "--json"]) == 1
    good = tmp_path / "ok.py"
    good.write_text("def f(xs=None):\n    return xs or []\n")
    assert ast_lint.main([str(good)]) == 0

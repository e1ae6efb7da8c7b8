"""The decode burst's compiled program holds no cache-shaped copy or slice
(ISSUE 26), reads the cache through one ragged kernel call per layer,
with no array of the bucket's shape left (ISSUE 30), and writes it from
inside that call, with no scatter into an array of the cache's shape
(ISSUE 32), checked by ``tools/burst_hlo_check.py``: its reading of an
HLO text on recorded snippets, and the burst itself compiled here,
without a chip, for a
described v5e at the benchmark configurations' widths (two layers: the
copies and the kernel calls are per layer, so two show what twenty-four
would; and once at the configurations' own depth for the scratch), with a
bucket and, as the chip runs it since ISSUE 31, without one.

The topology is described inside a fixture and nowhere else: only one
process may load the TPU's library, and each xdist worker imports this
file (see the on-chip-measurement guide, section 2)."""

import functools
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _tool():
    spec = importlib.util.spec_from_file_location(
        "burst_hlo_check", os.path.join(ROOT, "tools", "burst_hlo_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


HLO = """HloModule jit_fused_burst, is_scheduled=true, input_output_alias={ {3}: (12, {}, may-alias), {4}: (13, {}, may-alias) }, entry_computation_layout={()}

%fused_computation.1 (param_0.1: bf16[28,8,2048,128]) -> bf16[28,8,640,128] {
  %param_0.1 = bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %slice.9 = bf16[28,8,640,128]{3,2,1,0:T(8,128)(2,1)} slice(%param_0.1), slice={[0:28], [0:8], [0:640], [0:128]}
}

%body.2 (p: (bf16[28,8,2048,128])) -> (bf16[28,8,2048,128]) {
  %p = (bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)} get-tuple-element(%p), index=0
  %slice.4 = bf16[28,8,640,128]{3,1,2,0:T(8,128)(2,1)S(1)} slice(%gte.1), slice={[0:28], [0:8], [0:640], [0:128]}
  %fusion.7 = bf16[28,8,640,128]{3,2,1,0:T(8,128)(2,1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  ROOT %t = (bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)}) tuple(%gte.1)
}

%cond.3 (p: (bf16[28,8,2048,128])) -> pred[] {
  %p = (bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)}) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main.1 (k0: bf16[28,8,2048,128]) -> bf16[28,8,2048,128] {
  %k0 = bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %slice-start.1 = ((bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)}), bf16[7,8,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%k0), slice={[0:7], [0:8], [0:2048], [0:128]}
  %copy.630 = bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)} copy(%k0)
  %copy.2 = bf16[28,2048]{1,0:T(8,128)(2,1)} copy(%x)
  %tup = (bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)}) tuple(%copy.630)
  %while.1 = (bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)}) while(%tup), condition=%cond.3, body=%body.2
  %gte.9 = bf16[28,8,2048,128]{3,1,2,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=0
  ROOT %copy.681 = bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)} copy(%gte.9)
}
"""


def test_reader_names_cache_shaped_ops_and_where_they_sit():
    found = _tool().cache_shaped(HLO, 28, 8, (2048, 640), 128)
    assert sorted((op, res, inside) for op, res, inside, _ in found) == [
        ("copy", "bf16[28,8,2048,128]", False),
        ("copy", "bf16[28,8,2048,128]", False),
        ("slice", "bf16[28,8,640,128]", True),
        ("slice-start", "bf16[28,8,2048,128]", False),
    ]


def test_reader_leaves_a_fused_slice_and_other_shapes_alone():
    tool = _tool()
    clean = "\n".join(
        line for line in HLO.splitlines()
        if " copy(%k0)" not in line and "copy(%gte.9)" not in line
        and "slice-start(" not in line and "%slice.4 =" not in line
    )
    # the slice inside fused_computation.1 is an operand of its fusion,
    # and copy.2 is not the cache's shape
    assert tool.cache_shaped(clean, 28, 8, (2048, 640), 128) == []
    # another lane count is another array
    assert tool.cache_shaped(HLO, 4, 8, (2048, 640), 128) == []


def test_reader_counts_kernel_calls_and_bucket_shaped_arrays():
    tool = _tool()
    # the recorded burst is PR 25's: no kernel, and the bucket's slice as
    # an op of its own, as a fusion's result, and inside that fusion (its
    # root and its signature)
    assert tool.kernel_calls(HLO) == {"inside": 0, "outside": 0}
    assert tool.bucket_shaped(HLO, 28, 8, 640, 128) == 4
    assert tool.bucket_shaped(HLO, 4, 8, 640, 128) == 0
    call = (
        '  %ragged.1 = bf16[28,8,2,128]{3,2,1,0} custom-call(%l, %q, %gte.1,'
        ' %gte.1), custom_call_target="tpu_custom_call"\n')
    with_kernel = HLO.replace("  %slice.4 =", call + "  %slice.4 =").replace(
        "  %copy.2 =", call + "  %copy.2 =")
    assert tool.kernel_calls(with_kernel) == {"inside": 1, "outside": 1}


def test_reader_finds_the_mixers_bodies_their_kernels_and_their_per_lane_ops():
    """A Mamba run's ``while`` body as the parent of ISSUE 56 compiled it (the
    state kernel alone, and the tail's slice, ``a``'s copy, the kernel's
    operands as ops of their own) and as the issue leaves it."""
    tool = _tool()
    assert tool.mixer_bodies(HLO, 28, 5120, 4) == []
    body = """
%mamba.1 (p: (bf16[192,26,3,5120], f32[192,26,16,5120])) -> (bf16[192,26,3,5120], f32[192,26,16,5120]) {
  %p = (bf16[192,26,3,5120]{3,0,2,1}, f32[192,26,16,5120]{3,2,1,0}) parameter(0)
  %fusion.589 = bf16[192,10240]{1,0:T(8,128)(2,1)} fusion(%p), kind=kOutput, calls=%fused_computation.1
  %copy.174 = bf16[192,1,5120]{2,1,0:T(2,128)(2,1)} copy(%fusion.589)
  %dynamic-slice_bitcast_fusion.6 = bf16[192,3,5120]{2,1,0:T(4,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %bitcast.4 = f32[192,1,5120]{2,1,0} bitcast(%copy.174)
  %reshape.570 = f32[192,1,5120]{2,1,0:T(1,128)} reshape(%copy.174)
  %bitcast_add_fusion.15 = bf16[192,1,2560]{2,0,1:T(8,128)(2,1)} fusion(%p), kind=kOutput, calls=%fused_computation.1
  %selective_scan_step.15 = (f32[192,26,16,5120]{3,2,1,0}, f32[192,1,5120]{2,1,0}) custom-call(%p), custom_call_target="tpu_custom_call"
  ROOT %t = (bf16[192,26,3,5120]{3,0,2,1}, f32[192,26,16,5120]{3,2,1,0}) tuple(%p)
}
"""
    loop = ("  %while.9 = (bf16[192,26,3,5120]{3,0,2,1}, f32[192,26,16,5120]{3,2,1,0})"
            " while(%tup), condition=%cond.3, body=%mamba.1\n")
    hlo = HLO.replace("ENTRY", body + "\nENTRY", 1).replace(
        "  %gte.9 =", loop + "  %gte.9 =")
    (found,) = tool.mixer_bodies(hlo, 192, 5120, 4)
    assert found["body"] == "mamba.1"
    assert found["kernels"] == ["selective_scan_step"]
    # the residual stream's [192, 1, 2560] is no per-lane array of the mixer,
    # and a bitcast is no op
    assert [line.split(" = ")[0] for line in found["per_lane_ops"]] == [
        "%copy.174", "%dynamic-slice_bitcast_fusion.6", "%reshape.570"]
    # other lanes, another width: not these arrays
    assert tool.mixer_bodies(hlo, 64, 5120, 4)[0]["per_lane_ops"] == []
    assert tool.mixer_bodies(hlo, 192, 2048, 4)[0]["per_lane_ops"] == []
    tails = ('  %conv_tail_step.15 = (bf16[26,3,192,5120]{3,2,1,0}, bf16[192,5120]{1,0})'
             ' custom-call(%p), custom_call_target="tpu_custom_call"\n')
    after = "\n".join(
        line for line in hlo.splitlines()
        if not line.strip().startswith(("%copy.174", "%dynamic-slice_bitcast",
                                        "%reshape.570", "%bitcast.4"))
    ).replace("  %selective_scan_step.15 =", tails + "  %selective_scan_step.15 =")
    (found,) = tool.mixer_bodies(after, 192, 5120, 4)
    assert found["kernels"] == ["conv_tail_step", "selective_scan_step"]
    assert found["per_lane_ops"] == []


def test_reader_counts_scatters_into_the_cache():
    """The write before ISSUE 32, as the parent's burst compiled it: a
    scatter of ``Dh`` rows, the root of a fusion of its own, one for K and
    one for V a layer. The reader finds it inside the fusion; a scatter
    into another shape, or the word in a name or in metadata, is none."""
    tool = _tool()
    assert tool.cache_scatters(HLO, 28, 8, 2048, 128) == 0
    fused = """
%fused_computation.9 (param_0.6289: bf16[28,8,2048,128], param_1: s32[224,3], param_2: bf16[224,128]) -> bf16[28,8,2048,128] {
  %param_0.6289 = bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %scatter.3 = bf16[]{:T(256)} parameter(1), metadata={op_name="scatter"}
  ROOT %scatter.625 = bf16[28,8,2048,128]{3,2,1,0:T(8,128)(2,1)} scatter(%param_0.6289, %custom-call.150, %transpose.1120), update_window_dims={2}, inserted_window_dims={0,1,2}, scatter_dims_to_operand_dims={0,1,2}, index_vector_dim=2, to_apply=%region_2.3, metadata={op_name="jit(fused_burst)/while/body/closed_call/scatter"}
}
"""
    other = fused.replace("bf16[28,8,2048,128]", "bf16[28,92544]")
    assert tool.cache_scatters(HLO + fused, 28, 8, 2048, 128) == 1
    assert tool.cache_scatters(HLO + fused + fused, 28, 8, 2048, 128) == 2
    assert tool.cache_scatters(HLO + other, 28, 8, 2048, 128) == 0
    # fewer lanes than the scatter's operand has: another array
    assert tool.cache_scatters(HLO + fused, 4, 8, 2048, 128) == 0


def test_reader_counts_weight_slices_prefetched_from_an_hbm_temporary():
    """The burst before the layers' slices were tied to their input: a
    layer of the stacked ``wq``, cut into an HBM temporary by one op and
    brought to VMEM by a ``copy-start`` inside the ``while``. A copy of
    the whole stack, or one outside the loop, is not a layer's slice."""
    tool = _tool()
    assert tool.weight_slices_through_hbm(HLO) == 0
    prefetch = (
        "  %copy-start.3 = (bf16[1,2048,2048]{1,2,0:T(8,128)(2,1)S(1)},"
        " bf16[1,2048,2048]{1,2,0:T(8,128)(2,1)}, u32[]{:S(2)})"
        " copy-start(%get-tuple-element.9)\n")
    stack = prefetch.replace("bf16[1,", "bf16[24,")
    inside = HLO.replace("  %slice.4 =", prefetch + stack + "  %slice.4 =")
    assert tool.weight_slices_through_hbm(inside) == 1
    outside = HLO.replace("  %copy.2 =", prefetch + "  %copy.2 =")
    assert tool.weight_slices_through_hbm(outside) == 0


def test_reader_counts_the_weights_relaid_on_entry():
    """The parent's Mistral burst (PR 53), its entry computation: ``wq`` and
    ``wk`` relaid straight from the parameter, ``wv`` prefetched in its own
    order (a move, not a layout) and relaid from there: the 705 MB of
    ISSUE 54. A copy under 1 MiB, one inside the ``while``, one of what an
    op computed and a prefetch alone are none."""
    tool = _tool()
    # the recorded burst relays its cache on entry (%copy.630): that counts
    assert tool.weights_relaid_on_entry(HLO) == 2 * 28 * 8 * 2048 * 128
    clean = "\n".join(l for l in HLO.splitlines() if "%copy.630 =" not in l)
    assert tool.weights_relaid_on_entry(clean) == 0
    prefetch = (
        "  %params__blocks____wv__.1 = bf16[14,4096,1024]{2,1,0:T(8,128)(2,1)} parameter(8)\n"
        "  %copy-start = (bf16[14,4096,1024]{2,1,0:T(8,128)(2,1)S(1)}, bf16[14,4096,1024]"
        "{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%params__blocks____wv__.1)\n"
        "  %copy-done = bf16[14,4096,1024]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start)\n")
    relaid = (
        "  %params__blocks____wk__.1 = bf16[14,4096,1024]{2,1,0:T(8,128)(2,1)} parameter(5)\n"
        "  %params__blocks____wq__.1 = bf16[14,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(7)\n"
        "  %copy.300 = bf16[14,4096,1024]{1,2,0:T(8,128)(2,1)} copy(%params__blocks____wk__.1)\n"
        "  %copy.302 = bf16[14,4096,1024]{1,2,0:T(8,128)(2,1)} copy(%copy-done)\n"
        "  %copy.301 = bf16[14,4096,4096]{1,2,0:T(8,128)(2,1)} copy(%params__blocks____wq__.1),"
        ' backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[]}}\n')
    at = "  %copy.2 ="
    assert tool.weights_relaid_on_entry(clean.replace(at, prefetch + at)) == 0
    assert tool.weights_relaid_on_entry(
        clean.replace(at, prefetch + relaid + at)) == 704643072
    # evabyte's: a leaf prefetched in slices, joined, and relaid from there
    joined = (
        "  %params__layers___0___wq__.1 = bf16[4096,4096]{1,0:T(8,128)(2,1)} parameter(9)\n"
        "  %slice-start.1 = ((bf16[4096,4096]{1,0:T(8,128)(2,1)}), bf16[2048,4096]{1,0:T(8,128)"
        "(2,1)S(1)}, s32[]{:S(2)}) slice-start(%params__layers___0___wq__.1), slice={[0:2048], [0:4096]}\n"
        "  %slice-done.1 = bf16[2048,4096]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)\n"
        "  %custom-call.40 = bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done.1,"
        ' %slice-done.1), custom_call_target="ConcatBitcast"\n'
        "  %copy.77 = bf16[4096,4096]{0,1:T(8,128)(2,1)} copy(%custom-call.40)\n")
    assert tool.weights_relaid_on_entry(
        clean.replace(at, joined + at)) == 2 * 4096 * 4096
    # under 1 MiB; of what a fusion made; inside the while
    small = relaid.replace("14,4096,", "1,64,")
    assert tool.weights_relaid_on_entry(clean.replace(at, small + at)) == 0
    computed = relaid.replace(" parameter(5)", " fusion(%x), kind=kLoop")
    assert tool.weights_relaid_on_entry(
        clean.replace(at, prefetch + computed + at)) == 704643072 - 2 * 14 * 4096 * 1024
    inside = clean.replace("  %slice.4 =", relaid.split("\n")[2] + "\n  %slice.4 =")
    assert tool.weights_relaid_on_entry(inside) == 0


def test_reader_counts_the_aliases():
    assert _tool().alias_count(HLO) == 2
    assert _tool().alias_count("HloModule m, is_scheduled=true\n") == 0


def test_reader_tells_one_program_from_two_but_for_source_names():
    """``--against``: a change that must leave the dense bursts alone
    (ISSUE 33) compiles to the parent's program once what only names the
    source is left out: every op's metadata and the tables at the head."""
    tool = _tool()
    named = HLO.replace(
        "parameter(0)\n  ROOT %slice.9",
        'parameter(0), metadata={op_name="jit(x)" source_file="a.py" source_line=7}'
        "\n  ROOT %slice.9", 1)
    moved = named.replace("source_line=7", "source_line=90").replace(
        "\n\n", "\n\nFileNames\n1 \"a.py\"\n2 \"b.py\"\n\nFunctionNames\n1 \"f\"\n\n", 1)
    assert named != HLO and "FileNames" in moved
    same = tool.same_program(named, moved)
    assert same["hlo_equal_but_for_metadata"] and same["kernels_equal_but_for_locations"]
    assert same["first_differing_line"] is None and same["kernels"] == 0
    other = tool.same_program(HLO, HLO.replace("[0:640]", "[0:512]", 1))
    assert not other["hlo_equal_but_for_metadata"]
    assert "[0:640]" in other["first_differing_line"][0]
    # a kernel's body that differs and does not parse as the same MLIR is
    # never reached where the count of kernels differs
    call = ('  %r.1 = bf16[28,8,2,128]{3,2,1,0} custom-call(%l), custom_call_target='
            '"tpu_custom_call", backend_config={"custom_call_config": {"body":"QUJD"}}\n')
    more = tool.same_program(HLO, HLO.replace("  %slice.4 =", call + "  %slice.4 ="))
    assert not more["kernels_equal_but_for_locations"]


def test_reader_compares_while_bodies_up_to_constants():
    """Two compilations of one program differ in instruction names and in
    the values of constants (a burst's bucket is one); an instruction
    more or fewer is another program."""
    tool = _tool()
    body = HLO.replace(
        "  %slice.4 =", "  %c.1 = s32[] constant(640)\n  %slice.4 =")
    renamed = body.replace("%slice.4", "%slice.77").replace(
        "constant(640)", "constant(2048)")
    assert tool.while_body_diff(body, renamed) == []
    # outside the while nothing is compared
    assert tool.while_body_diff(
        body, body.replace("  %copy.2 = ", "  %copy.3 = ")) == []
    extra = body.replace(
        "  %slice.4 =", "  %b.1 = s32[28]{0} broadcast(%c.1), dimensions={}\n  %slice.4 =")
    assert tool.while_body_diff(body, extra) == [
        "+ % = s32[28]{0} broadcast(%), dimensions={}"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("attn_len", [640, None])
@pytest.mark.parametrize("config", ["internlm2-1.8b", "mistral-7b-v0.3"])
def test_burst_compiled_for_v5e_keeps_the_cache_in_place(
        one_chip, config, attn_len):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = config
    cfg["num_hidden_layers"] = 2
    out = _tool().check(cfg, attn_len, one_chip)
    assert out["cache_shaped_copies_and_slices"] == {}, out["example"]
    assert out["input_output_aliases"] >= out["cache_leaves"] == 4
    assert out["alias_size_in_bytes"] >= out["cache_bytes"]
    # no scratch of the cache's size: two layers' K and V here
    assert out["temp_size_in_bytes"] < out["cache_bytes"] / 2
    # the read: this process's backend is the CPU, the burst is lowered
    # for the v5e, and it holds the kernel, once a layer, not the dots
    assert out["kernel_calls"] == {"inside": 2, "outside": 0}
    assert out["bucket_shaped_arrays"] == 0
    # the write: the kernel lands the step's rows, no scatter does
    assert out["cache_shaped_scatters"] == 0
    assert out["weight_slices_through_hbm"] == 0
    assert out["ok"]


@pytest.mark.parametrize("attn_len", [1280, None])
@pytest.mark.parametrize("config", ["internlm2-1.8b", "mistral-7b-v0.3"])
def test_burst_at_full_depth_takes_no_more_scratch_than_before(
        one_chip, config, attn_len):
    """At the configuration's own depth, at the deepest warmed bucket but
    one and without a bucket: a kernel call per layer, the cache aliased
    through, and ``temp_size_in_bytes`` not above the burst's before the
    ragged read."""
    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = config
    out = tool.check(
        cfg, attn_len, one_chip, temp_limit=tool.TEMP_BEFORE[config])
    layers = cfg["num_hidden_layers"]
    assert out["kernel_calls"] == {"inside": layers, "outside": 0}
    assert out["cache_shaped_scatters"] == 0
    assert out["weight_slices_through_hbm"] == 0
    assert out["input_output_aliases"] >= out["cache_leaves"] == 2 * layers
    assert out["temp_size_in_bytes"] <= out["temp_size_before"]
    assert out["ok"], out


# temp_size_in_bytes of the parent's burst (PR 53) at the configuration's own
# depth without a bucket, for a described v5e: all but 4-7 MB of it the
# q / k / v weights (evabyte: and the head) relaid on entry
TEMP_PR53 = {"internlm2-1.8b": 409104896, "mistral-7b-v0.3": 708608512,
             "evabyte": 838731776}


@pytest.mark.parametrize("config", list(TEMP_PR53))
def test_the_burst_is_handed_its_weights_as_its_projections_consume_them(
        one_chip, config):
    """ISSUE 54: compiled on the tree the batcher hands it
    (``burst_params``), the burst relays no weight on entry, and its scratch
    is under the parent's by at least the bytes the family holds relaid."""
    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = config
    out = tool.check(cfg, None, one_chip)
    relaid = {"internlm2-1.8b": 2 * 24 * 2048 * (2048 + 2 * 1024),
              "mistral-7b-v0.3": 2 * 14 * 4096 * (4096 + 2 * 1024),
              "evabyte": 2 * 4096 * (24 * 4096 + 2560)}[config]
    assert out["burst_layout_bytes"] == relaid
    assert out["weights_relaid_on_entry"] == 0
    assert out["temp_size_in_bytes"] <= TEMP_PR53[config] - relaid
    assert out["ok"], out


def test_qwen3_next_burst_compiled_for_v5e_is_kernels_over_a_cache_in_place(one_chip):
    """The configuration's own burst (its lanes, all 8 layers, no bucket):
    the two full layers decode through the ragged kernel at a head of 256
    with 8 query heads a KV head, every linear layer's state through the
    state kernel and every layer's held experts through the touched-expert
    kernel, all inside the ``while``; keys, values, states and tails are
    aliased through, and nothing of a cache leaf's shape is copied or
    sliced out."""
    import re

    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "qwen3-next-80b-a3b"
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, kv, T, dh, leaves) == (
        cfg["server"]["slots"], 2, 4096, 256, 16)
    # keys and values of 2 layers, state and tail of 6
    assert cache_bytes == lanes * (2 * 2 * 2 * 4096 * 256 * 2
                                + 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2))
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 2 + 6 + 8, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("gated_delta_step") == 6
    assert names.count("touched_experts_ffn") == 8
    assert tool.cache_shaped(hlo, lanes, kv, (T,), dh) == []
    assert tool.cache_shaped(hlo, lanes, 32, (128,), 128) == []    # the state
    assert tool.cache_scatters(hlo, lanes, kv, T, dh) == 0
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 256 << 20


# the cache's length (a prompt past 3584) and the padded length the longbatch
# mix's 3328-token prompts prefill at (``DecoderFamily.prefill_lengths``)
@pytest.mark.parametrize("T", [4096, 3584])
def test_qwen3_next_prefill_compiled_for_v5e_is_one_delta_kernel_a_linear_layer(
        one_chip, T):
    """The configuration's own prefill of one prompt at ``T`` rows
    (all 8 layers, counters and all): each of the 6 linear layers' gated
    delta rule is ONE ``gated_delta_prefill`` kernel call, and no ``while``
    is left under that scope (off a TPU it is a ``lax.scan`` over the
    bucket's 64 chunks); the loops that remain are the held experts' (a
    pass over the room and its bands, 2 a layer)."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark import manifest
    from seldon_core_tpu.models.llm import DecoderLM

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    kwargs = manifest.architecture(
        ROOT, manifest.load(ROOT), cfg["architecture"]).model_kwargs(cfg, 0)
    kwargs.pop("seed")
    model = DecoderLM(**kwargs)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.dtype(model.cfg.dtype)),
        jax.eval_shape(model.init_params, 0))
    assert T <= cfg["server"]["max_seq"]
    hlo = jax.jit(lambda p, t, last: model.prefill_counted(p, t, T, last)).lower(
        params, sds((1, T), jnp.int32), sds((1,), jnp.int32)).compile().as_text()
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("gated_delta_prefill") == 6
    loops = [line for line in hlo.splitlines() if re.search(r" while\(", line)]
    assert len(loops) == 2 * 8
    assert not [line for line in loops if "gated_delta_prefill" in line]
    scoped = [line for line in hlo.splitlines()
              if "gated_delta_prefill" in line and "op_name=" in line]
    assert scoped and not [line for line in scoped if "/while/" in line]


EXPERT_CUTS = {
    # a dense layer, a window and a full expert layer; a DeltaNet period;
    # the dense layer and two expert layers
    "trinity-mini": [0, 6, 7],
    "qwen3-next-80b-a3b": [0, 1, 2, 3],
    "joyai-llm-flash": [0, 1, 2],
}


@pytest.mark.parametrize("config", list(EXPERT_CUTS))
def test_the_expert_cells_burst_is_one_program_with_and_without_the_grouped_kernel(
        one_chip, config, monkeypatch):
    """The grouped prefill's kernel (PR 43) is in no decode burst: the
    burst of each expert cell, compiled for the v5e, is the same program
    but for metadata whether the grouped path's shapes are the kernel's or
    (as in the parent) never are, and names no ``grouped_swiglu`` call."""
    import jax

    from seldon_core_tpu.ops import experts

    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = config
    cfg["served_layers"] = EXPERT_CUTS[config]
    cfg["num_hidden_layers"] = len(cfg["served_layers"])
    mine = tool.compile_burst(cfg, None, one_chip)[0].as_text()
    assert "touched_experts_ffn" in mine and "grouped_swiglu" not in mine
    monkeypatch.setattr(experts, "groups_in_kernel", lambda *a, **kw: False)
    jax.clear_caches()       # ``decode_experts`` is traced anew, the dots in it
    parents = tool.compile_burst(cfg, None, one_chip)[0].as_text()
    jax.clear_caches()
    same = tool.same_program(mine, parents)
    assert same["hlo_equal_but_for_metadata"], same["first_differing_line"]
    assert same["kernels_equal_but_for_locations"] and same["kernels"] > 0


@pytest.mark.parametrize("T", [4096, 3584])
def test_trinity_mini_prefill_compiled_for_v5e_is_one_grouped_kernel_a_layer(
        one_chip, T):
    """The configuration's own prefill of one prompt at ``T`` rows (all
    6 layers, counters and all): each of the 4 expert layers' grouped
    experts is ONE ``grouped_swiglu`` call over the ``8 T`` sorted pairs,
    and no ``ragged-dot`` is left."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark import manifest
    from seldon_core_tpu.models.llm import DecoderLM

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        cfg = json.load(f)
    kwargs = manifest.architecture(
        ROOT, manifest.load(ROOT), cfg["architecture"]).model_kwargs(cfg, 0)
    kwargs.pop("seed")
    model = DecoderLM(**kwargs)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.dtype(model.cfg.dtype)),
        jax.eval_shape(model.init_params, 0))
    assert T <= cfg["server"]["max_seq"]
    compiled = jax.jit(
        lambda p, t, last: model.prefill_counted(p, t, T, last)).lower(
        params, sds((1, T), jnp.int32), sds((1,), jnp.int32)).compile()
    hlo = compiled.as_text()
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("grouped_swiglu") == 4
    assert "ragged-dot" not in hlo and "ragged_dot" not in hlo
    # the sorted rows and the weighted products of a layer, in bfloat16:
    # nothing [8 T, 1024] is written for the SwiGLU, in any type
    assert not re.search(rf"= \w+\[{8 * T},1024\]", hlo)
    # the parent's scratch (2,297 MiB; most of it outside the experts)
    assert compiled.memory_analysis().temp_size_in_bytes < 2400 << 20


def test_joyai_llm_flash_burst_compiled_for_v5e_is_kernels_over_a_latent_cache_in_place(one_chip):
    """The configuration's own burst (64 lanes, all 12 layers, no bucket):
    every layer decodes through the ragged latent kernel (one [64, 6144,
    640] array a layer, read as key and as value) and every expert layer's
    held experts through the touched-expert kernel at a width of 768, all
    inside the ``while``; the rows are aliased through, and nothing of a
    cache leaf's shape is copied, sliced or scattered into."""
    import re

    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "joyai-llm-flash"
    compiled, (lanes, _kv, T, _dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, T, leaves) == (64, 6144, 12)
    assert cache_bytes == 64 * 6144 * 12 * 640 * 2
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 12 + 11, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("latent_decode_attention") == 12
    assert names.count("touched_experts_ffn") == 11
    leaf = re.compile(r" = bf16\[\d+,6144,640\][^ ]* (copy|copy-start|slice|"
                      r"slice-start|scatter|dynamic-update-slice)\(")
    assert not [line for line in hlo.splitlines() if leaf.search(line)]
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 512 << 20


def test_flash_kernel_compiled_for_v5e_takes_keys_of_192_and_values_of_128(one_chip):
    """The latent family's prefill attention: the flash kernel at a key
    width of 192 and a value width of 128, in the bucket the traffic uses
    most and in the comparison's, under the family's kernel name."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import _tile, flash_attention

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    for t in (1792, 6144):
        block_q, block_k = _tile(t, t)
        fn = jax.jit(functools.partial(
            flash_attention, causal=True, block_q=block_q, block_k=block_k,
            name="latent_prefill_attention"))
        compiled = fn.lower(sds((1, 32, t, 192)), sds((1, 32, t, 192)),
                            sds((1, 32, t, 128))).compile()
        call, = (line for line in compiled.as_text().splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line)
        assert "latent_prefill_attention" in call
        assert f"bf16[32,{t},128]" in call


def test_evabyte_burst_compiled_for_v5e_is_one_kernel_a_layer_over_a_ring_in_place(one_chip):
    """The configuration's own burst (20 lanes, all 8 layers, no bucket):
    every layer decodes through the ragged kernel over its ring and its
    summaries (four arrays a layer, two lengths), inside the ``while``; all
    32 leaves are aliased through; nothing of the ring's shape is copied,
    sliced, scattered into or carried in another layout (a gather of a
    chunk's rows from the ring made the compiler relay every layer's ring,
    a ring-sized copy a layer and step), and nothing of the summaries'
    either: the kernel pools the chunk a step completes and lands the row
    itself, so NO op but its call writes a summary array (the model's
    step scattered the rows until PR 45: 16 scatters a step)."""
    import re

    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs", "evabyte.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "evabyte"
    lanes = cfg["server"]["slots"]
    compiled, (n, _kv, T, _dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (n, T, leaves) == (lanes, 16384, 32)
    assert cache_bytes == lanes * 8 * (2048 + 1024) * 16384
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 8, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("eva_decode_attention") == 8
    ring = re.compile(rf" = bf16\[{lanes},32,2048,128\][^ ]* (copy|copy-start|"
                      r"slice|slice-start|scatter|dynamic-update-slice|gather)\(")
    assert not [line for line in hlo.splitlines() if ring.search(line)]
    # one layout for the ring everywhere: positions second-minor
    assert not re.search(rf"bf16\[{lanes},32,2048,128\]\{{3,1,0,2", hlo)
    summ = re.findall(rf" = bf16\[{lanes},32,1024,128\][^ ]* ([a-z\-]+)\(", hlo)
    assert set(summ) <= {"parameter", "get-tuple-element", "bitcast"}
    assert "scatter" not in summ
    assert not re.search(rf"bf16\[{lanes},32,1024,128\]\{{3,1,0,2", hlo)
    # the kernel's call takes and returns all four of a layer's arrays
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "eva_decode_attention" in line]
    assert len(calls) == 8
    for call in calls:
        assert call.count(f"bf16[{lanes},32,1024,128]") >= 4
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    # (it held the weights' relayout once a burst, 24 x 33.5 MB, until the
    # burst was handed them relaid: the limit that holds since is
    # test_the_burst_is_handed_its_weights_as_its_projections_consume_them's)
    assert mem.temp_size_in_bytes < 1 << 30


def test_flash_kernel_compiled_for_v5e_behind_a_visible_prefix(one_chip):
    """The evabyte family's prefill attention: a window of 2048 queries
    behind the 896 summary rows of the earlier windows, the visible count
    prefetched: one Mosaic call under the family's kernel name."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import _tile, flash_attention

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    block_q, block_k = _tile(2048, 2944, None, 896)
    assert block_k > 128 and 896 % block_k      # the prefix ends mid-tile
    fn = jax.jit(lambda q, k, v, n: flash_attention(
        q, k, v, causal=True, prefix=896, prefix_len=n, block_q=block_q,
        block_k=block_k, name="eva_prefill_attention"))
    compiled = fn.lower(sds((1, 32, 2048, 128)), sds((1, 32, 2944, 128)),
                        sds((1, 32, 2944, 128)), sds((), jnp.int32)).compile()
    call, = (line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line)
    assert "eva_prefill_attention" in call and "bf16[32,2048,128]" in call


@pytest.mark.parametrize("heads,t,dh,window", [
    (32, 1792, 128, None),    # mistral docqa: 512 does not divide, a lead tile
    (32, 2048, 128, None),
    (16, 1024, 128, None),    # internlm2
    (32, 4096, 128, 2048),    # trinity-mini's band
    (32, 4096, 128, None),
    (16, 4096, 256, None),    # qwen3-next: whole K and V of 2 MiB each resident
    (32, 3584, 128, 2048),    # the lengths a prompt past 1792 pads to: seven
    (16, 3584, 256, None),    # key tiles, and four
    (16, 2048, 256, None),
    (8, 1024, 64, None),
])
def test_flash_kernel_compiled_for_v5e_at_the_rules_tile(one_chip, heads, t, dh, window):
    """Each family's largest prefill attention at the tile ``attention()``
    picks for it (``_tile``): Mosaic takes the unequal tile, the lead tile
    and the band, inside the scoped VMEM every kernel lives under."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import _tile, flash_attention

    x = jax.ShapeDtypeStruct((1, heads, t, dh), jnp.bfloat16, sharding=one_chip)
    block_q, block_k = _tile(t, t, window)
    assert (block_q, block_k) == (256, 512)
    compiled = jax.jit(functools.partial(
        flash_attention, causal=True, block_q=block_q, block_k=block_k,
        window=window)).lower(x, x, x).compile()
    call, = (line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line)
    assert f"bf16[{heads},{t},{dh}]" in call


def test_sdar_burst_compiled_for_v5e_is_a_block_kernel_a_layer_over_a_cache_in_place(one_chip):
    """The configuration's own burst of passes (32 lanes, all 6 layers, a
    block of 4 positions a lane, no bucket): every layer reads and writes
    its cache through the ragged kernel's block entry, under its own name,
    beside the touched-experts kernel, inside the ``while``; all 12 cache
    leaves are aliased through; NO pass copies, slices, scatters into or
    relays anything of the cache's shape (a pass lands four rows a lane and
    layer: the kernel's, not a scatter's), and the block registers ride in
    the loop's carry."""
    import re

    tool = _tool()
    with open(os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "sdar-30b-a3b"
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, kv, T, dh, leaves) == (32, 4, 4096, 128, 12)
    assert cache_bytes == 32 * 4096 * 12288
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_fused_burst,")
    assert not tool.cache_shaped(hlo, lanes, kv, (T,), dh)
    assert tool.cache_scatters(hlo, lanes, kv, T, dh) == 0
    assert not re.search(rf"bf16\[{lanes},{kv},{T},{dh}\]\{{3,1,", hlo)
    # an attention and an expert kernel a layer, every one inside the loop
    assert tool.kernel_calls(hlo) == {"inside": 12, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("block_decode_attention") == 6
    assert names.count("touched_experts_ffn") == 6
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "block_decode_attention" in line]
    for call in calls:
        # the queries of a block ride as 4 x 8 rows a KV head
        assert f"bf16[{lanes},{kv},32,{dh}]" in call
        assert call.count(f"bf16[{lanes},{kv},{T},{dh}]") >= 4
    assert tool.weight_slices_through_hbm(hlo) == 0
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 256 << 20


def test_flash_kernel_compiled_for_v5e_under_the_block_mask(one_chip):
    """The sdar_moe family's prefill attention: the flash kernel at the
    rule's tile with the mask open inside blocks of 4, in the cell's
    buckets; and the same call without a block is the program it was."""
    import functools

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import _tile, flash_attention

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    for t in (512, 1792, 4096):
        block_q, block_k = _tile(t, t)
        texts = []
        for block in (4, None):
            fn = jax.jit(functools.partial(
                flash_attention, causal=True, block_q=block_q,
                block_k=block_k, block=block))
            compiled = fn.lower(*(sds((1, 32, t, 128)),) * 3).compile()
            call, = (line for line in compiled.as_text().splitlines()
                     if "custom-call(" in line and "tpu_custom_call" in line)
            assert f"bf16[32,{t},128]" in call
            texts.append(call)
        assert texts[0] != texts[1]


def _configured(one_chip, name):
    """``(configuration, model, parameter shapes, sds)`` of a configuration's
    file, the parameters as shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    from benchmark import manifest
    from seldon_core_tpu.models.llm import DecoderLM

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = name
    kwargs = manifest.architecture(
        ROOT, manifest.load(ROOT), cfg["architecture"]).model_kwargs(cfg, 0)
    kwargs.pop("seed")
    model = DecoderLM(**kwargs)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, jnp.dtype(model.cfg.dtype)),
        jax.eval_shape(model.init_params, 0))
    return cfg, model, params, sds


def _lfm2(one_chip):
    return _configured(one_chip, "lfm2-24b-a2b")


def test_lfm2_burst_compiled_for_v5e_is_the_kernel_over_two_heads_a_row_in_place(one_chip):
    """The configuration's own burst (64 lanes of 16,384, all 13 layers, no
    bucket): the three attention layers decode through the ragged kernel
    (not the dots) over rows of two heads of 64, 4 rows of 128 with 8 query
    rows each, every expert layer's held experts through the touched-expert
    kernel at width 1,536, all inside the ``while``; keys, values and the
    ten convolution layers' tails are aliased through, and nothing of a
    cache leaf's shape is copied or sliced out: no cache-sized copy a
    step."""
    import re

    tool = _tool()
    cfg, _model, _params, _sds = _lfm2(one_chip)
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, kv, T, dh, leaves) == (64, 8, 16384, 64, 3 + 3 + 10)
    # keys and values of 3 layers (6,144 B a position), tails of 10
    assert cache_bytes == lanes * (3 * 2 * 8 * 64 * 2 * T + 10 * 2 * 2048 * 2)
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 3 + 12, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("touched_experts_ffn") == 12
    assert names.count("ragged_decode_attention") == 3
    # the kernel's call: 4 rows of two heads, 8 query rows each, 128 wide
    call = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "ragged_decode_attention" in line)
    assert "bf16[64,4,8,128]" in call and "bf16[64,4,16384,128]" in call
    # as the cache lies ([lanes, 4, T, 128]) and as the config names it
    for rows, width in ((4, 128), (8, 64)):
        assert tool.cache_shaped(hlo, lanes, rows, (T,), width) == []
        assert tool.cache_scatters(hlo, lanes, rows, T, width) == 0
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 256 << 20


def test_lfm2_prefill_compiled_for_v5e_is_flash_at_head_64_and_grouped_at_1536(
        one_chip, monkeypatch):
    """The configuration's own prefill of one prompt in the 8192 bucket (all
    13 layers, counters and all), lowered as on a TPU: the three attention
    layers through the flash kernel at a head of 64 and the rule's tile,
    every expert layer's held experts through ONE grouped kernel call a
    layer at width 1,536 (an expert's three matrices whole, 18.9 MB, two
    slots apiece, inside its 64 MB of VMEM)."""
    import re

    import jax
    import jax.numpy as jnp

    cfg, model, params, sds = _lfm2(one_chip)
    # ``ops.attention`` asks the process's backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T = 8192
    hlo = jax.jit(lambda p, t, last: model.prefill_counted(p, t, T, last)).lower(
        params, sds((1, T), jnp.int32), sds((1,), jnp.int32)).compile().as_text()
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("grouped_swiglu") == 12
    flash = [line for line in hlo.splitlines()
             if "custom-call(" in line and "flash_attention" in line]
    assert len(flash) == 3 and all(f"bf16[32,{T},64]" in line for line in flash)
    # the tails come out at the prompts' own lengths: [10, 1, 2, 2048]
    assert "bf16[10,1,2,2048]" in hlo


def _jamba(one_chip):
    return _configured(one_chip, "jamba2-3b")


def test_jamba_burst_compiled_for_v5e_is_three_scanned_runs_over_a_state_in_place(one_chip):
    """The configuration's own burst (the cell's lanes of 8,192, all 28
    layers, no bucket): the 26 Mamba layers are three scanned runs, so the
    program holds THREE tails kernels and THREE state kernels (one of each
    a run's body) and the two attention layers' ragged kernels at 20 query
    rows on one KV head, all inside the ``while``; keys, values, the tails
    [lanes, 26, 3, 5120] and the float32 state [lanes, 26, 16, 5120] are
    aliased through, and nothing of the state's or the tails' shape is copied
    or sliced out: no layer's state or tails leave their array, and between
    a mixer's matrix products no op of its own holds a per-lane [lanes, 1,
    5120] or [lanes, 3, 5120] (ISSUE 56)."""
    import re

    tool = _tool()
    cfg, _model, _params, _sds = _jamba(one_chip)
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, kv, T, dh, leaves) == (
        cfg["server"]["slots"], 1, 8192, 128, 2 + 2 + 1 + 1)
    # 1,024 B a position, 358,400 B a lane and Mamba layer
    assert cache_bytes == lanes * (T * 1024 + 26 * 358_400)
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 3 + 3 + 2, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("conv_tail_step") == 3
    assert names.count("selective_scan_step") == 3
    assert names.count("ragged_decode_attention") == 2
    call = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "ragged_decode_attention" in line)
    assert f"bf16[{lanes},1,20,128]" in call and f"bf16[{lanes},1,8192,128]" in call
    step = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "selective_scan_step" in line)
    assert f"f32[{lanes},26,16,5120]" in step
    assert "output_to_operand_aliasing" in step
    # x, delta and y as they lie, [lanes, 5120] in bfloat16; b and c [16, lanes]
    assert step.count(f"bf16[{lanes},5120]") >= 3
    assert step.count(f"bf16[16,{lanes}]") == 2 and "f32[192,1,5120]" not in step
    tails = next(line for line in hlo.splitlines()
                 if "custom-call(" in line and "conv_tail_step" in line)
    # the tails as they lie between executables, a layer's tap [lanes, 5120];
    # ``a`` where it lies in the in-projection's product
    assert f"bf16[26,3,{lanes},5120]" in tails
    assert f"bf16[{lanes},10240]" in tails
    assert "output_to_operand_aliasing" in tails
    # a run's body: the two kernels and no per-lane op of its own
    bodies = tool.mixer_bodies(hlo, lanes, 5120, 4)
    assert len(bodies) == 3
    for body in bodies:
        assert body["kernels"] == ["conv_tail_step", "selective_scan_step"]
        assert body["per_lane_ops"] == []
    assert tool.cache_shaped(hlo, lanes, 1, (T,), 128) == []
    assert tool.cache_scatters(hlo, lanes, 1, T, 128) == 0
    # the state and the tails themselves: no copy, slice or dynamic-slice of
    # a layer's [lanes, 16 | 3, 5120] or of the whole array, a fusion's
    # inside included, whichever way round the tails are shown
    assert not re.search(
        rf"f32\[{lanes},(26|1),16,5120\][^\n]*? (copy|slice|dynamic-slice)\(", hlo)
    assert not re.search(
        rf"bf16\[({lanes},(26|1),3|26,3,{lanes}|{lanes},3),5120\][^\n]*? "
        r"(copy|slice|dynamic-slice|transpose)\(", hlo)
    assert tool.alias_count(hlo) >= leaves
    # what is still relaid at the burst's top: ``wq`` and ``W_x`` (S12 b),
    # not the tails' 153 MB each way
    assert tool.weights_relaid_on_entry(hlo) < 80 << 20
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 512 << 20


def test_jamba_prefill_compiled_for_v5e_is_a_scan_kernel_a_run_and_flash_at_20_heads(
        one_chip, monkeypatch):
    """The configuration's own prefill of eight prompts in the 1024 bucket
    (all 28 layers, counters and all), lowered as on a TPU: one
    ``selective_scan_prefill`` kernel a scanned run, the two attention
    layers through the flash kernel at 20 heads of 128 without a rotary; the
    states and tails come out at the prompts' own lengths, one array a kind
    over the 26 layers."""
    import re

    import jax
    import jax.numpy as jnp

    _cfg, model, params, sds = _jamba(one_chip)
    # ``ops.attention`` asks the process's backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T = 8, 1024
    hlo = jax.jit(lambda p, t, last: model.prefill_counted(p, t, T, last)).lower(
        params, sds((B, T), jnp.int32), sds((B,), jnp.int32)).compile().as_text()
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("selective_scan_prefill") == 3
    flash = [line for line in hlo.splitlines()
             if "custom-call(" in line and "flash_attention" in line]
    assert len(flash) == 2 and all(f"bf16[{B * 20},{T},128]" in line
                                   for line in flash)
    assert f"f32[1,{B},26,16,5120]" in hlo and f"bf16[1,{B},26,3,5120]" in hlo
    assert "sine" not in hlo and "cosine" not in hlo


def _mimo(one_chip):
    return _configured(one_chip, "mimo-v2.5")


def test_mimo_burst_compiled_for_v5e_is_two_named_reads_over_a_cache_of_kinds_in_place(one_chip):
    """The configuration's own burst (64 lanes of 12,288, all 7 layers, no
    bucket): the two full layers decode through the ragged kernel at 16
    query rows a KV head over keys CUT at 128 (4 rows of parts and 2 of two
    heads' 64-wide rests each a position, the queries' rests 32 rows a
    packed row) beside values of 128, the five window layers through the
    same kernel under its own name over a RING of 128 rows with 8 KV heads
    (8 + 4 key rows) and a sink, every expert layer's held experts
    through the touched-expert kernel at width 2,048, all inside the
    ``while``; every kind's leaves are aliased through, and nothing of a
    full layer's leaf's shape is copied, sliced out or scattered into: no
    cache-sized copy a step, and no window layer holds a ``max_seq``-long
    array."""
    import re

    tool = _tool()
    cfg, _model, _params, _sds = _mimo(one_chip)
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = tool.compile_burst(
        cfg, None, one_chip)
    assert (lanes, kv, T, dh, leaves) == (64, 4, 12288, 192, 2 * 2 + 2 * 5)
    # as allocated: what holds something; 2,560 B a position and full
    # layer, and five rings of 128 rows of 5,120 B a lane
    assert cache_bytes == lanes * (2 * 4 * (192 + 128) * 2 * T
                                   + 5 * 8 * (192 + 128) * 2 * 128)
    hlo = compiled.as_text()
    assert tool.kernel_calls(hlo) == {"inside": 7 + 6, "outside": 0}
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("touched_experts_ffn") == 6
    assert names.count("ragged_decode_attention") == 2
    assert names.count("swa_ring_attention") == 5
    full = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "ragged_decode_attention" in line)
    assert "bf16[64,4,16,128]" in full and "bf16[64,6,12288,128]" in full
    assert "bf16[64,4,12288,128]" in full and "bf16[64,2,32,128]" in full
    assert "256]" not in full.split("custom-call(")[1].split(")")[0]
    ring = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "swa_ring_attention" in line)
    # a layer's ring is an array of its own, 128 rows a lane
    assert "bf16[64,12,128,128]" in ring and "bf16[64,8,128,128]" in ring
    assert "bf16[64,8,8,128]" in ring and "bf16[64,4,16,128]" in ring
    assert "f32[8,8,1]" in ring and "12288" not in ring.split("custom-call(")[1]
    # (a layer's KEY ring, 33.5 MB and all of it read by a step of 64 lanes
    # past the window, the compiler may prefetch to VMEM before the call
    # and copy back after it: on the chip one layer's of five, 0.02 ms of a
    # 12.2 ms step: PERF.md section 6, PR 57)
    for rows, length, width in ((6, T, 128), (4, T, 128)):
        assert tool.cache_shaped(hlo, lanes, rows, (length,), width) == []
        assert tool.cache_scatters(hlo, lanes, rows, length, width) == 0
    assert tool.alias_count(hlo) >= leaves
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 256 << 20


def test_mimo_prefill_compiled_for_v5e_is_flash_at_two_widths_a_sink_and_a_band(
        one_chip, monkeypatch):
    """The configuration's own prefill of one prompt in the 9728 bucket (all
    7 layers, counters and all), lowered as on a TPU: the two full layers
    through the flash kernel at keys of 192 beside values of 128, the five
    window layers through it under their own name with the band of 128 and
    the sinks (9,728 keys of one head resident: past the default scoped
    VMEM, which the call asks for); the rings come out 128 rows long. An
    expert of 4096 x 2048 (50 MB, two slots apiece 101 MB) does not fit the
    grouped kernel's VMEM whole: the held experts' rows go through it in
    two slices of the width (``ops.experts.width_slices``), a call a slice
    in each of the six expert layers, and no ``ragged_dot``'s loop is left."""
    import re

    import jax
    import jax.numpy as jnp

    cfg, model, params, sds = _mimo(one_chip)
    # ``ops.attention`` asks the process's backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T = 9728
    compiled = jax.jit(
        lambda p, t, last: model.prefill_counted(p, t, T, last)).lower(
        params, sds((1, T), jnp.int32), sds((1,), jnp.int32)).compile()
    hlo = compiled.as_text()
    names = re.findall(r"%([a-z_]+)[.\d]* = [^\n]*? custom-call\(", hlo)
    assert names.count("grouped_swiglu") == 6 * 2
    assert names.count("swa_prefill_attention") == 5
    assert names.count("flash_attention") == 2
    band = next(line for line in hlo.splitlines()
                if "custom-call(" in line and "swa_prefill_attention" in line)
    assert f"bf16[64,{T},192]" in band and f"bf16[64,{T},128]" in band
    assert "f32[64]" in band
    # the slab: the full layers' rows and the last 128 rows of the window's
    # (keys cut and packed: 4 + 2 rows a position, 8 + 4 a ring's)
    assert f"bf16[2,1,6,{T},128]" in hlo and "bf16[5,1,12,128,128]" in hlo
    assert f"bf16[5,1,8,{T}" not in hlo
    # beside 11.9 GB of weights and cache the transients have 4 GB
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 << 30, temp

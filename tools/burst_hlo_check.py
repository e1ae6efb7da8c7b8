#!/usr/bin/env python3
"""Structural check of the decode burst's compiled program: does the KV
cache go through ``jit_fused_burst`` in place?

Compiles ``ContinuousBatcher._burst_fn`` (``steps_per_poll`` fused decode
steps) from shapes alone at a benchmark configuration file's sizes
(``server.slots`` lanes x ``server.max_seq`` positions, every layer) and
reads the optimised HLO:

* a ``copy`` / ``copy-start`` / ``slice`` / ``slice-start`` instruction of
  its own (not inside a fusion) whose result is ``[n <= lanes, KV, T or
  attn_len, Dh]`` is a cache-shaped array being written out: FAIL;
* ``temp_size_in_bytes`` beside the cache's bytes (a burst that copies the
  cache reserves scratch as large as the cache), and at a configuration's
  own depth not above what the burst took before the ragged read (ISSUE 30);
* the input-output aliases (one per donated cache leaf, or donation bought
  nothing);
* the decode read (ISSUE 30): the ``while`` body holds one Mosaic kernel
  call per layer (``ops.decode_attention``, chosen by the platform the
  burst is lowered for), and nothing anywhere in the module, inside a
  fusion or out, has the bucket's shape ``[n <= lanes, KV, attn_len, Dh]``:
  the two dots that read every lane's whole bucket are gone;
* the decode write (ISSUE 32): a ``scatter`` anywhere in the module, inside
  a fusion or out, whose operand (and so result) is ``[n <= lanes, KV, T,
  Dh]`` is a step's rows going into the cache by an op of their own: FAIL.
  The kernel call above lands them, the cache aliased in and out of it;
* the weights (ISSUE 32, ROADMAP S12): a ``copy-start`` inside the ``while``
  whose result is one layer of a stacked weight, ``[1, a, b]``, is that
  layer's slice written to an HBM temporary and prefetched from there,
  read twice and written once where one read would do: FAIL. A layer's
  slices are tied to its input (``decode_step_ragged_list``) and go
  straight into VMEM;
* the weights' layout (ISSUE 54, ROADMAP S12): a ``copy`` / ``copy-start``
  in the entry computation, outside the ``while``, of 1 MiB or more of
  bf16 whose operand is a parameter, or comes from one through nothing but
  moves, is a weight relaid at the top of every burst, written to HBM and
  read back where the stored bytes never change: its bytes are printed
  (``weights_relaid_on_entry``), and FAIL where the configuration's family
  states the layout its burst consumes (``DecoderFamily.burst_params``:
  the burst is compiled on that tree, as the batcher hands it over);
* the Mamba mixers (ISSUE 56; a configuration with ``mamba_d_conv``): each
  scanned run's ``while`` body (the computation that calls
  ``selective_scan_step``) holds exactly two Mosaic kernels,
  ``conv_tail_step`` and ``selective_scan_step``, and no instruction of its
  own whose result is ``[lanes, 1, C]`` or ``[lanes, K - 1, C]``: a slice,
  a copy, a cast or a reshape of a per-lane array between the mixer's
  matrix products (what a fusion that feeds a dot holds inside is the
  dot's). The burst then needs one kernel call an attention layer and two a
  run, not one a layer.

The burst is checked with ``attn_len=None`` (what the chip runs since
ISSUE 31: where the read takes each lane's length the executable has no
bucket) and at two buckets (what a platform without the kernel's shapes
still warms). With ``--hlo-dir`` the output also says whether the
``while`` bodies at ``None`` and at the first bucket differ in anything
but constants. With ``--against DIR`` beside it, each HLO is compared with
the one of its name that another tree's ``--hlo-dir DIR`` kept: one
program where they are equal once what only names the source is left out
(the tables of files, functions and stack frames at the head, every op's
``metadata={...}``, and the locations inside each Mosaic kernel's
serialised body: an edit above a kernel in its file moves every line
number in the bytecode), else FAIL. A change that must leave a
configuration's burst alone shows it so.

    python tools/burst_hlo_check.py                      # on the chip
    python tools/burst_hlo_check.py --described v5e:2x2  # no chip: the TPU
        compiler targets a described device (JAX_PLATFORMS=cpu); the HLO
        is the chip's, a time is not

Exit 0 clean, 1 any of the above, 3 skipped (no TPU and no ``--described``).
A skip is not a pass.
"""

from __future__ import annotations

import argparse
import base64
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIGS = ("benchmark/configs/internlm2-1.8b.json",
           "benchmark/configs/mistral-7b-v0.3.json")
ATTN_LENS = (None, 640, 1280)
OFFENDERS = ("copy", "copy-start", "slice", "slice-start")
# temp_size_in_bytes of the burst at these configurations' own depth before
# the ragged read, for a described v5e at either ATTN_LENS (PR 26's burst;
# tools/burst_hlo_check.py at commit 21ef44d)
TEMP_BEFORE = {"internlm2-1.8b": 689441280, "mistral-7b-v0.3": 1332189696}
_INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_CALLED_RE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_TABLE_RE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)")
_BODY_RE = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def computations(hlo: str) -> dict:
    """``{name: [instruction lines]}``, and the entry's name under ``""``."""
    comps: dict = {}
    cur = None
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = re.match(r"^(ENTRY )?%?([\w.\-]+)", line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    comps[""] = cur
            continue
        if line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def scheduled(comps: dict) -> dict:
    """The computations whose instructions run as ops of their own: the
    entry and, transitively, loop bodies and conditions and called
    computations. What a ``fusion`` calls is the inside of one op.
    ``{name: inside_while}``."""
    out = {comps[""]: False}
    todo = [comps[""]]
    while todo:
        name = todo.pop()
        for line in comps[name]:
            m = _INSTR_RE.match(line)
            if not m or m.group(3) == "fusion":
                continue
            loop = m.group(3) == "while"
            for callee in _CALLED_RE.findall(line):
                if callee in comps and callee not in out:
                    out[callee] = out[name] or loop
                    todo.append(callee)
    return out


def cache_shaped(hlo: str, lanes: int, kv: int, lengths, dh: int) -> list:
    """The offending instructions: ``[op, result, inside_while, line]``."""
    shape = re.compile(
        r"^\(*bf16\[(\d+),%d,(%s),%d\]" % (kv, "|".join(map(str, lengths)), dh)
    )
    comps = computations(hlo)
    found = []
    for name, inside in scheduled(comps).items():
        for line in comps[name]:
            m = _INSTR_RE.match(line)
            if not m or m.group(3) not in OFFENDERS:
                continue
            res = shape.match(m.group(2))
            if res and int(res.group(1)) <= lanes:
                found.append([m.group(3), m.group(2).split("{")[0].lstrip("("),
                              inside, line.strip()[:160]])
    return found


def _is_kernel(m, line: str) -> bool:
    """Whether the instruction ``_INSTR_RE`` matched is a Mosaic kernel call."""
    return bool(m) and m.group(3) == "custom-call" \
        and 'custom_call_target="tpu_custom_call"' in line


def kernel_calls(hlo: str) -> dict:
    """Mosaic kernel calls that run as ops of their own:
    ``{"inside": n, "outside": m}`` the ``while``."""
    comps = computations(hlo)
    out = {"inside": 0, "outside": 0}
    for name, inside in scheduled(comps).items():
        for line in comps[name]:
            if _is_kernel(_INSTR_RE.match(line), line):
                out["inside" if inside else "outside"] += 1
    return out


_NO_OP = ("bitcast", "get-tuple-element", "parameter", "tuple", "constant")


def mixer_bodies(hlo: str, lanes: int, channels: int, taps: int) -> list:
    """The Mamba runs' ``while`` bodies (each scheduled computation that
    calls a ``selective_scan_step`` kernel): ``{"body", "kernels": the
    Mosaic kernels it calls, in order, "per_lane_ops": its own instructions
    whose result is [lanes, 1, channels] or [lanes, taps - 1, channels]}``."""
    comps = computations(hlo)
    shape = re.compile(r"^\(*\w+\[%d,(1|%d),%d\]" % (lanes, taps - 1, channels))
    out = []
    for name, inside in scheduled(comps).items():
        kernels, per_lane = [], []
        for line in comps[name] if inside else ():
            m = _INSTR_RE.match(line)
            if not m:
                continue
            if _is_kernel(m, line):
                kernels.append(re.sub(r"[.\d]+$", "", m.group(1)))
            elif m.group(3) not in _NO_OP and shape.match(m.group(2)):
                per_lane.append(line.strip()[:160])
        if "selective_scan_step" in kernels:
            out.append({"body": name, "kernels": kernels,
                        "per_lane_ops": per_lane})
    return out


def cache_scatters(hlo: str, lanes: int, kv: int, T: int, dh: int) -> int:
    """``scatter`` instructions into an array of the cache's shape ``[n <=
    lanes, KV, T, Dh]``, anywhere in the module: XLA wraps the write's
    scatter in a fusion of its own, so the fusions' insides are read too."""
    return sum(
        int(n) <= lanes
        for n in re.findall(
            r"= bf16\[(\d+),%d,%d,%d\]\S* scatter\(" % (kv, T, dh), hlo)
    )


def weight_slices_through_hbm(hlo: str) -> int:
    """``copy-start`` instructions inside the ``while`` whose result is one
    layer of a stacked weight, ``[1, a, b]``: the prefetch into VMEM of a
    slice that an earlier op wrote to an HBM temporary."""
    comps = computations(hlo)
    n = 0
    for name, inside in scheduled(comps).items():
        for line in comps[name] if inside else ():
            m = _INSTR_RE.match(line)
            if m and m.group(3) == "copy-start" \
                    and re.match(r"^\(\w+\[1,\d+,\d+\]", m.group(2)):
                n += 1
    return n


# ops that hand their operand on as it is, or move it: what lies between a
# parameter and the copy that relays it (a prefetch into fast memory in
# slices, joined by a ``ConcatBitcast`` custom call)
_MOVES = ("copy", "copy-start", "copy-done", "slice-start", "slice-done",
          "bitcast", "custom-call", "get-tuple-element", "tuple")
_RELAID_MIN = 1 << 20


def weights_relaid_on_entry(hlo: str) -> int:
    """Bytes of bf16 that ``copy`` / ``copy-start`` instructions of the
    entry computation (outside the ``while``: once a burst) write, 1 MiB or
    more each, in another order of dimensions than their operand has, where
    the operand is a parameter or comes from one through ``_MOVES`` alone: a
    weight the burst relays before its first step. (A copy in the operand's
    own order is a prefetch into another memory, not a layout.)"""
    comps = computations(hlo)
    made: dict = {}
    for line in comps[comps[""]]:
        m = _INSTR_RE.match(line)
        if m:
            args = line[m.end():].split(")", 1)[0]
            made[m.group(1)] = (m.group(3), m.group(2),
                                re.findall(r"%([\w.\-]+)", args))

    def from_parameter(name):
        op, _result, operands = made.get(name, ("", "", []))
        return op == "parameter" or (
            op in _MOVES and any(from_parameter(o) for o in operands))

    def laid(result):
        """``(dims, minor-to-major)`` of a result's (first) bf16 array."""
        m = re.match(r"^\(*bf16\[([\d,]+)\]\{([\d,]+)", result)
        return m.groups() if m else None

    total = 0
    for op, result, operands in made.values():
        mine = laid(result)
        if op not in ("copy", "copy-start") or not mine:
            continue
        size = 2
        for d in mine[0].split(","):
            size *= int(d)
        if size >= _RELAID_MIN and any(
                from_parameter(o) and laid(made[o][1]) != mine
                for o in operands):
            total += size
    return total


def bucket_shaped(hlo: str, lanes: int, kv: int, attn_len: int, dh: int) -> int:
    """Arrays of the bucket's shape ``[n <= lanes, KV, attn_len, Dh]``
    anywhere in the module, a fusion's inside included: the slice the
    bucket's dots took as a fused operand was one."""
    return sum(
        int(n) <= lanes
        for n in re.findall(r"bf16\[(\d+),%d,%d,%d\]" % (kv, attn_len, dh), hlo)
    )


def while_body_lines(hlo: str) -> list:
    """The instructions that run inside the ``while``, fusions' insides
    included, with what varies between two compilations of one program
    taken out: instruction names, metadata, and the values of constants."""
    comps = computations(hlo)
    names = [n for n, inside in scheduled(comps).items() if inside]
    seen = set(names)
    while names:
        for line in comps[names.pop()]:
            for callee in _CALLED_RE.findall(line):
                if callee in comps and callee not in seen:
                    seen.add(callee)
                    names.append(callee)
    out = []
    for name in seen:
        for line in comps[name]:
            line = re.sub(r", metadata=\{[^}]*\}", "", line.strip())
            line = re.sub(r"constant\([^)]*\)", "constant(_)", line)
            out.append(re.sub(r"%[\w.\-]+", "%", line))
    return sorted(out)


def while_body_diff(hlo_a: str, hlo_b: str) -> list:
    """Lines of one ``while`` body that the other lacks (``-`` of a,
    ``+`` of b), after ``while_body_lines`` took the constants out: empty
    where the two bursts are one device program."""
    a = collections.Counter(while_body_lines(hlo_a))
    b = collections.Counter(while_body_lines(hlo_b))
    return sorted(["- " + l for l in (a - b).elements()]
                  + ["+ " + l for l in (b - a).elements()])


def kernel_text(body: str) -> str:
    """A Mosaic kernel's MLIR, from the custom call's base64 bytecode,
    without debug locations."""
    from jax._src import tpu_custom_call  # noqa: F401  (loads the dialects)
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def without_source_names(hlo: str) -> tuple:
    """``(lines without metadata and the head's tables, kernel bodies in
    order)``."""
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    bodies = _BODY_RE.findall(hlo)
    hlo = _BODY_RE.sub('"body":"<kernel>"', hlo)
    lines, in_table = [], False
    for line in hlo.splitlines():
        if _TABLE_RE.match(line):
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            lines.append(line)
    return lines, bodies


def same_program(hlo_a: str, hlo_b: str) -> dict:
    """Whether two optimised HLO texts are one program but for what names
    the source: the lines, and the kernels decoded without locations."""
    (a, a_k), (b, b_k) = without_source_names(hlo_a), without_source_names(hlo_b)
    kernels = len(a_k) == len(b_k) and all(
        x == y or kernel_text(x) == kernel_text(y) for x, y in zip(a_k, b_k))
    first = next(([x[:240], y[:240]] for x, y in zip(a, b) if x != y), None)
    return {"lines": len(a), "kernels": len(a_k),
            "hlo_equal_but_for_metadata": a == b,
            "kernels_equal_but_for_locations": kernels,
            "first_differing_line": first}


def alias_count(hlo: str) -> int:
    """Entries of the module's ``input_output_alias={ {3}: (12, {}, may-alias), ...}``."""
    header = hlo.split("\n", 1)[0]
    return len(re.findall(r"\{\d+\}: \(\d+, \{\}", header))


def served_model(cfg: dict):
    """The configuration's model, as the benchmark builds it."""
    from benchmark import manifest
    from seldon_core_tpu.models.llm import DecoderLM

    kwargs = manifest.architecture(
        ROOT, manifest.load(ROOT), cfg["architecture"]).model_kwargs(cfg, 0)
    kwargs.pop("seed")
    return DecoderLM(**kwargs)


def burst_layout_bytes(model) -> int:
    """Bytes (bf16) of the leaves that the family's ``burst_params`` holds
    in a layout of its own, beside the stored ones: 0 where the burst takes
    the stored tree."""
    import jax

    stored = jax.eval_shape(model.init_params, 0)
    derived = jax.eval_shape(model.burst_params, stored)
    have = {jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(stored)[0]}
    return sum(2 * leaf.size for path, leaf in
               jax.tree_util.tree_flatten_with_path(derived)[0]
               if jax.tree_util.keystr(path) not in have)


def compile_burst(cfg: dict, attn_len, device_sharding):
    """``_burst_fn`` compiled from shapes at the configuration's sizes, on
    the tree the batcher hands its bursts (``burst_params``): nothing is
    allocated, so this needs no device memory."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    model = served_model(cfg)
    mc = model.cfg
    lanes, T = cfg["server"]["slots"], cfg["server"]["max_seq"]

    class Executables(ContinuousBatcher):
        """The executables are closures of the constructor; its own device
        state is one lane of 128 positions, and it is given no parameter:
        none to touch, and none to derive the burst's tree from."""

        def _derive_burst_params(self):
            self._burst_params = self.params

    batcher = Executables(model, {}, slots=1, max_seq=128)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=device_sharding)

    dt = jnp.dtype(mc.dtype)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, dt),
        jax.eval_shape(model.burst_params,
                       jax.eval_shape(model.init_params, 0)))
    # the cache as the batcher carries it (``cache_layers``): a dict of
    # kinds, each a list of one array a layer that has the kind
    cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.cache_layers(lanes, T)))
    leaves = jax.tree_util.tree_leaves(cache)
    lane_i32 = sds((lanes,), jnp.int32)
    lane_state = (sds((lanes,), jnp.bool_), sds((lanes,), jnp.float32),
                  sds((lanes, 2), jnp.uint32))
    if batcher._block_w > 1:
        # generation by blocks: the burst of passes over the lanes' blocks
        regs = jax.tree_util.tree_map(
            lambda a: sds((lanes,) + a.shape[1:], a.dtype),
            batcher._block_regs)
        lowered = batcher._block_burst_fn.lower(
            params, cache, regs, lane_i32, *lane_state, batcher._k, attn_len,
            False)
    else:
        lowered = batcher._burst_fn.lower(
            params, cache, lane_i32, lane_i32, *lane_state, batcher._k,
            attn_len)
    compiled = lowered.compile()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    return (compiled, (lanes, mc.n_kv_heads, T, mc.head_dim), cache_bytes,
            len(leaves))


def check(cfg: dict, attn_len, device_sharding, hlo_dir=None,
          temp_limit=None) -> dict:
    """``attn_len``: a bucket, or None for the burst without one.
    ``temp_limit``: ``TEMP_BEFORE``'s entry where ``cfg`` has the
    configuration's own depth (``main`` passes it), none for a cut one."""
    compiled, (lanes, kv, T, dh), cache_bytes, leaves = compile_burst(
        cfg, attn_len, device_sharding)
    hlo = compiled.as_text()
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{cfg['name']}.{attn_len}.hlo.txt"), "w") as f:
            f.write(hlo)
    mem = compiled.memory_analysis()
    bounded = attn_len is not None and attn_len < T
    found = cache_shaped(
        hlo, lanes, kv, (T, attn_len) if bounded else (T,), dh)
    kinds: dict = {}
    for op, result, inside, _line in found:
        key = f"{op} {result} {'inside' if inside else 'outside'} the while"
        kinds[key] = kinds.get(key, 0) + 1
    layers = cfg["num_hidden_layers"]
    aliases = alias_count(hlo)
    kernels = kernel_calls(hlo)
    bucket = bucket_shaped(hlo, lanes, kv, attn_len, dh) if bounded else 0
    scatters = cache_scatters(hlo, lanes, kv, T, dh)
    two_hop = weight_slices_through_hbm(hlo)
    relaid = weights_relaid_on_entry(hlo)
    layout_bytes = burst_layout_bytes(served_model(cfg))
    mixers = None
    if cfg.get("mamba_d_conv"):
        # one kernel an attention layer, two a scanned run of Mamba layers
        mixers = mixer_bodies(
            hlo, lanes, cfg["mamba_expand"] * cfg["hidden_size"],
            cfg["mamba_d_conv"])
        attention = sum(
            i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            for i in range(layers))
        layers = attention + 2 * len(mixers)
    return {
        "lanes": lanes, "attn_len": attn_len,
        "cache_shaped_copies_and_slices": kinds,
        "example": found[0][3] if found else None,
        "cache_bytes": cache_bytes,
        "temp_size_in_bytes": mem.temp_size_in_bytes,
        "temp_size_before": temp_limit,
        "alias_size_in_bytes": mem.alias_size_in_bytes,
        "input_output_aliases": aliases,
        "cache_leaves": leaves,
        "kernel_calls": kernels,
        "bucket_shaped_arrays": bucket,
        "cache_shaped_scatters": scatters,
        "weight_slices_through_hbm": two_hop,
        "weights_relaid_on_entry": relaid,
        "burst_layout_bytes": layout_bytes,
        **({"mixer_bodies": mixers} if mixers is not None else {}),
        "ok": not found and not scatters and not two_hop
        and not (layout_bytes and relaid)
        and aliases >= leaves
        and mem.alias_size_in_bytes >= cache_bytes
        and kernels == {"inside": layers, "outside": 0} and not bucket
        and all(m["kernels"] == ["conv_tail_step", "selective_scan_step"]
                and not m["per_lane_ops"] for m in mixers or ())
        and (temp_limit is None or mem.temp_size_in_bytes <= temp_limit),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", nargs="*", default=list(CONFIGS))
    ap.add_argument("--attn-len", nargs="*", default=list(ATTN_LENS),
                    type=lambda v: None if v.lower() == "none" else int(v),
                    help="buckets, or 'none' for the burst without one")
    ap.add_argument("--described", metavar="TOPOLOGY",
                    help="compile for a described TPU (e.g. v5e:2x2), no chip")
    ap.add_argument("--hlo-dir", help="keep each optimised HLO here")
    ap.add_argument("--against", metavar="DIR",
                    help="another tree's --hlo-dir: each HLO kept here must "
                         "be the program of its name there")
    args = ap.parse_args(argv)
    if args.against and not args.hlo_dir:
        ap.error("--against compares what --hlo-dir keeps")

    if args.described:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.sharding import SingleDeviceSharding

    if args.described:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=args.described)
        device = topo.devices[0]
    elif jax.default_backend() == "tpu":
        device = jax.devices()[0]
    else:
        print(f"burst_hlo_check: SKIPPED, backend is {jax.default_backend()!r}"
              " (run on the chip, or pass --described v5e:2x2)")
        return 3
    print(f"burst_hlo_check: compiling for {device.device_kind}"
          f"{' (described, not attached)' if args.described else ''}")
    ok = True
    for path in args.config:
        with open(os.path.join(ROOT, path)) as f:
            cfg = json.load(f)
        cfg["name"] = os.path.basename(path)[:-len(".json")]
        for attn_len in args.attn_len:
            out = check(cfg, attn_len, SingleDeviceSharding(device),
                        args.hlo_dir, TEMP_BEFORE.get(cfg["name"]))
            print(json.dumps({"config": cfg["name"], **out}))
            ok = ok and out["ok"]
            if args.against:
                name = f"{cfg['name']}.{attn_len}.hlo.txt"
                with open(os.path.join(args.hlo_dir, name)) as f, \
                        open(os.path.join(args.against, name)) as g:
                    same = same_program(g.read(), f.read())
                print(json.dumps({"config": cfg["name"], "against": name, **same}))
                ok = (ok and same["hlo_equal_but_for_metadata"]
                      and same["kernels_equal_but_for_locations"])
        buckets = [a for a in args.attn_len if a is not None]
        if args.hlo_dir and None in args.attn_len and buckets:
            texts = []
            for attn_len in (None, buckets[0]):
                with open(os.path.join(
                        args.hlo_dir,
                        f"{cfg['name']}.{attn_len}.hlo.txt")) as f:
                    texts.append(f.read())
            diff = while_body_diff(*texts)
            print(json.dumps({
                "config": cfg["name"],
                "while_body": f"None against {buckets[0]}",
                "differs_beyond_constants": bool(diff),
                "differing_lines": len(diff), "first": diff[:6],
            }))
    print("burst_hlo_check:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

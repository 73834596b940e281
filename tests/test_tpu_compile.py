"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e at
the paper model's widths (``paper-mpfp-100m``: d_model 768, 12 heads of 64,
d_ff 3072, vocab 32000), on a described ``v5e:2x2`` topology — no chip; and
of the paged decode and prefill steps at the widths of the benchmark's
models, ChatGLM3-6B and DeepSeek-V2-Lite.

Interpret mode accepts kernels that Mosaic refuses (grid / dimension-
semantics mismatches, unaligned tiles, VMEM overruns); these compiles catch
that class of fault before any chip time is spent.  Each test asserts the
compiled program holds the Mosaic kernel (``tpu_custom_call``), named by
its ``pallas_call`` ``name=`` (the name the device trace's op text shows).

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, so every test worker must
collect the same tests and only the one given this file loads it.
"""
import dataclasses
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.core import dispatch
from repro.core.limbs import PrelimbedWeight
from repro.core.policy import PrecisionPolicy
from repro.kernels import mp_attention, ops
from repro.models import transformer as T
from repro.models.mla import MLADims
from repro.models.moe import MoEDims
from repro.serve.engine import (POOL_ARGNUMS, make_paged_decode_step,
                                make_paged_prefill_step, pool_entry_shapes,
                                prelimb_dense_params)

CFG = get_config("paper-mpfp-100m")
D, FF, V = CFG.d_model, CFG.d_ff, CFG.vocab
H, DH = CFG.n_heads, CFG.resolved_head_dim
TRAIN_TOKENS = 8 * 128      # batch 8 x seq 128
DECODE_SLOTS = 8
BLOCK, POOL_BLOCKS, TABLE_W = 16, 129, 8   # paged pool of the smoke server


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    import os

    from jax.experimental import topologies

    # the TPU library logs under /tmp unless given a directory ("disabled"
    # still leaves its driver log there): keep its logs with the test's
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a persistent-cache entry compiled for a described chip cannot be read
    # back without one: keep these compiles out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes, kernel: str) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(", text), kernel
    return text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("mode", ["M8", "M23", "M36"])
def test_fused_matmul(one_chip, mode):
    _compiled_text(lambda a, b: ops.mp_matmul_pallas(a, b, mode), one_chip,
                   ((TRAIN_TOKENS, D), F32), ((D, FF), F32),
                   kernel="mp_matmul_fused")


def test_fused_multi_swiglu(one_chip):
    _compiled_text(
        lambda x, wg, wu: ops.mp_fused_proj_pallas(x, (wg, wu), "M16",
                                                   gate="swiglu"),
        one_chip, ((TRAIN_TOKENS, D), F32), ((D, FF), F32), ((D, FF), F32),
        kernel="mp_matmul_fused_multi")


def test_prelimbed_b(one_chip):
    _compiled_text(
        lambda x, wl: ops.mp_matmul_prelimbed_weights(x, wl, "M23"),
        one_chip, ((DECODE_SLOTS, 1, D), F32), ((3, D, FF), BF16),
        kernel="mp_matmul_prelimbed")


def test_mixed_prelimbed(one_chip):
    _compiled_text(
        lambda x, wl, ln, lo: ops.mp_mixed_matmul_pallas(
            x, PrelimbedWeight(wl), "M23", ln, lo),
        one_chip, ((DECODE_SLOTS, 1, D), F32), ((3, D, FF), BF16),
        ((DECODE_SLOTS,), I32), ((DECODE_SLOTS,), I32),
        kernel="mp_matmul_mixed_prelimbed")


def test_decompose(one_chip):
    _compiled_text(lambda w: ops.decompose_weights(w, 3), one_chip,
                   ((D, V), F32), kernel="mp_limb_decompose")


@pytest.mark.parametrize("batch,seq", [(1, 64), (8, 128)],
                         ids=["prefill", "train"])
def test_flash_attention(one_chip, batch, seq):
    qkv = ((batch, seq, H, DH), F32)
    _compiled_text(
        lambda q, k, v: mp_attention.mp_attention_pallas(q, k, v, "M16",
                                                         "M23"),
        one_chip, qkv, qkv, qkv, kernel="mp_flash_attention")


POOL = ((POOL_BLOCKS, BLOCK, H, DH), F32)
TABLE = ((DECODE_SLOTS, TABLE_W), I32)
SLOTS = ((DECODE_SLOTS,), I32)


def test_paged_attention(one_chip):
    _compiled_text(
        lambda q, kp, vp, t, ln: mp_attention.mp_paged_attention_pallas(
            q, kp, vp, t, ln, "M23"),
        one_chip, ((DECODE_SLOTS, H, DH), F32), POOL, POOL, TABLE, SLOTS,
        kernel="mp_paged_attention")


def test_mixed_paged_attention(one_chip):
    _compiled_text(
        lambda q, kp, vp, t, ln, a, b, c, d:
            mp_attention.mp_mixed_paged_attention_pallas(
                q, kp, vp, t, ln, "M23", "M23", a, b, c, d),
        one_chip, ((DECODE_SLOTS, H, DH), F32), POOL, POOL, TABLE, SLOTS,
        SLOTS, SLOTS, SLOTS, SLOTS, kernel="mp_mixed_paged_attention")


# ChatGLM3-6B at its published widths (bench/configs/chatglm3-6b-3l.json),
# two layers: d_ff 13696 is not a multiple of the kernel's 256-wide blocks
GLM = ModelConfig(name="chatglm3-6b-2l", family="dense", n_layers=2,
                  d_model=4096, vocab=65024, n_heads=32, n_kv_heads=2,
                  head_dim=128, d_ff=13696, rope_theta=10000.0,
                  rope_fraction=0.5, norm_eps=1e-5, max_seq=8192)
MM_FAMILY = Path(__file__).resolve().parents[1] / "bench" / "kernels" \
    / "mp_matmul.json"


def test_paged_decode_reads_limb_stacks_in_place(one_chip, monkeypatch):
    """The paged decode step on prelimbed M23 weights hands every matmul
    kernel its layer's stored limb stack: no ``pad`` and no
    ``dynamic-slice`` makes a bf16 limb stack, and each prelimbed kernel
    still shows the operands the benchmark's mp-matmul family matches
    (activations f32[M,K], weights bf16[n,K,N]), which
    ``mm_roofline.decode`` reads."""
    monkeypatch.setattr(dispatch, "kernel_interpret", lambda backend: False)
    policy = PrecisionPolicy.serve_default().overlay("M23")
    put = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    raw = jax.eval_shape(lambda k: T.init_params(GLM, k),
                         jax.random.PRNGKey(0))
    params = put(jax.eval_shape(lambda p: prelimb_dense_params(p, 3), raw))
    slots, blocks = 32, 129
    pool = ((GLM.n_layers, blocks, BLOCK, GLM.n_kv_heads, 128), F32)
    shapes = [pool, pool, ((slots, TABLE_W), I32), ((slots,), I32),
              ((slots, 1), I32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    step = dispatch.pin_backend(make_paged_decode_step(GLM, policy), "pallas")
    text = jax.jit(step).lower(params, *args).compile().as_text()

    copies = re.findall(r"= bf16\[\d+,\d+,\d+\]\S* (?:pad|dynamic-slice)\(|"
                        r"%(?:pad|dynamic-slice)\S*fusion\S* = bf16\[\d+,\d+,\d+\]",
                        text)
    assert not copies, copies
    calls = [line for line in text.splitlines()
             if re.search(r"%mp_matmul_prelimbed(\.\d+)? = .* custom-call\(",
                          line)]
    assert len(calls) == 7 * GLM.n_layers + 1  # qkv, o, gate, up, down; head
    patterns = json.loads(MM_FAMILY.read_text())["patterns"]
    for line in calls:
        assert any(re.search(p, line) for p in patterns), line[:200]


def test_latent_paged_attention(one_chip):
    """DeepSeek-V2-Lite's latent pool: 16 heads over c_kv 512 + rope 64,
    blocks of 64 positions."""
    _compiled_text(
        lambda ql, qr, c, r, t, ln: mp_attention.mp_latent_paged_attention_pallas(
            ql, qr, c, r, t, ln, "M23", "M23", scale=0.115),
        one_chip, ((64, 16, 512), F32), ((64, 16, 64), F32),
        ((129, 64, 512), F32), ((129, 64, 64), F32), ((64, 40), I32),
        ((64,), I32), kernel="mp_latent_paged_attention")


# DeepSeek-V2-Lite at its published widths (bench/configs/
# deepseek-v2-lite-5l.json), its dense layer and one MoE layer holding 8 of
# its 64 experts
DSV2 = ModelConfig(
    name="deepseek-v2-lite-2l", family="moe", n_layers=2, d_model=2048,
    vocab=102400, n_heads=16, n_kv_heads=16, d_ff=1408, norm_eps=1e-6,
    first_k_dense=1, dense_ff=10944, max_seq=2560,
    mla=MLADims(d_model=2048, n_heads=16, kv_lora=512, qk_nope_dim=128,
                qk_rope_dim=64, v_head_dim=128, yarn_factor=40.0,
                yarn_mscale=0.707, yarn_mscale_all_dim=0.707),
    moe=MoEDims(d_model=2048, n_experts=64, top_k=6, expert_ff=1408,
                n_shared=2, norm_topk_prob=False, n_held=8))
FAMILIES = Path(__file__).resolve().parents[1] / "bench" / "kernels"


def test_moe_mla_paged_decode_reads_limb_stacks_in_place(one_chip,
                                                         monkeypatch):
    """The MoE + MLA paged decode step on prelimbed M23 weights: no ``pad``
    or ``dynamic-slice`` makes a bf16 limb stack; every projection, head,
    expert and the lm_head runs the prelimbed kernel on its own stored
    stack; the latent kernel runs once a layer; and the benchmark's kernel
    families find the held experts' matmuls and the latent kernel."""
    monkeypatch.setattr(dispatch, "kernel_interpret", lambda backend: False)
    policy = PrecisionPolicy.serve_default().overlay("M23")
    put = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    raw = jax.eval_shape(lambda k: T.init_params(DSV2, k),
                         jax.random.PRNGKey(0))
    params = put(jax.eval_shape(
        lambda p: prelimb_dense_params(p, 3, mla=DSV2.mla), raw))
    slots, blocks, bs = 64, 129, 64
    shapes = [((2, blocks, bs, 512), F32), ((2, blocks, bs, 64), F32),
              ((slots, 8), I32), ((slots,), I32), ((slots, 1), I32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    step = dispatch.pin_backend(make_paged_decode_step(DSV2, policy), "pallas")
    text = jax.jit(step).lower(params, *args).compile().as_text()

    copies = re.findall(r"= bf16\[\d+,\d+,\d+\]\S* (?:pad|dynamic-slice)\(|"
                        r"%(?:pad|dynamic-slice)\S*fusion\S* = bf16\[\d+,\d+,\d+\]",
                        text)
    assert not copies, copies
    calls = [line for line in text.splitlines()
             if re.search(r"%mp_matmul_prelimbed(\.\d+)? = .* custom-call\(",
                          line)]
    # per layer: w_q, w_dkv, w_kr, 16 + 16 heads, w_o; the dense layer's
    # gate, up, down; the MoE layer's router, 3 shared, 8 x 3 experts; head
    assert len(calls) == 2 * 36 + 3 + 1 + 3 + 24 + 1
    latent = [line for line in text.splitlines() if re.search(
        r"%mp_latent_paged_attention(\.\d+)? = .* custom-call\(", line)]
    assert len(latent) == DSV2.n_layers
    fams = {f: [re.compile(p) for p in json.loads(
        (FAMILIES / f"{f}.json").read_text())["patterns"]]
        for f in ("moe_expert", "mla_attention")}
    hits = lambda f, lines: [x for x in lines
                             if any(p.search(x) for p in fams[f])]
    assert len(hits("moe_expert", calls)) == 24
    assert hits("mla_attention", latent) == latent
    assert not hits("mla_attention", calls)


# the benchmark cells' pool: 2561 blocks, so it does not fit the chip's
# on-core memory and stays where it lies
POOL_BLOCKS_CELL = 2561
# DeepSeek-V2-Lite with two MoE layers after its dense one: the prefill's
# MoE stack then scans
DSV2_3L = dataclasses.replace(DSV2, name="deepseek-v2-lite-3l", n_layers=3)
POOL_OPS = {"parameter", "bitcast", "scatter", "fusion", "get-tuple-element",
            "tuple", "while"}


_INSTRUCTION = re.compile(r"^\s*(ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def _instructions(text):
    """(computation, name, result type, opcode, is root) of every HLO
    instruction."""
    out, comp = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m:
            out.append((comp, m.group(2), m.group(3), m.group(4),
                        bool(m.group(1))))
    return out


@pytest.mark.parametrize("cfg,phase", [(GLM, "decode"), (GLM, "prefill"),
                                       (DSV2_3L, "decode"),
                                       (DSV2_3L, "prefill")],
                         ids=["glm-decode", "glm-prefill", "dsv2-decode",
                              "dsv2-prefill"])
def test_donated_paged_step_updates_the_pool_in_place(one_chip, monkeypatch,
                                                      cfg, phase):
    """The paged step jitted as the engine jits it (the pool donated,
    ``POOL_ARGNUMS``): both pool parameters alias the step's pool outputs;
    no op makes an f32 array shaped like the pool, a layer of it or one
    layer's slice — only bitcasts, the in-place scatter of the new rows and
    the layer scan's carry touch it, so no slice, pad, concatenate or
    dynamic-update-slice moves pool bytes; and the attention kernel reads
    the whole pool by global block id, the latent kernel's operands still
    the benchmark's ``mla_attention`` family.

    One exception, DeepSeek's rope-key pool: the chip's default layout of
    ``f32[L,N,64,64]`` puts the block axis minor, so the step copies the
    whole array into the kernel's row-major layout on entry and back on
    exit — two copies, no more.  (A pool kept row-major between steps
    avoids them, but an executable taking that layout does not survive
    the persistent compile cache: reloaded, it expects the default
    layout.)"""
    monkeypatch.setattr(dispatch, "kernel_interpret", lambda backend: False)
    policy = PrecisionPolicy.serve_default().overlay("M23")
    put = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    if phase == "decode":
        params = jax.eval_shape(
            lambda p: prelimb_dense_params(p, 3, mla=cfg.mla), params)
    L, N = cfg.n_layers, POOL_BLOCKS_CELL
    bs = 64 if cfg.mla is not None else BLOCK
    entries = pool_entry_shapes(cfg)
    pools = [jax.ShapeDtypeStruct((L, N, bs) + e, F32, sharding=one_chip)
             for e in entries]
    slots, toks = (64, ((64, 1), I32)) if phase == "decode" else (
        1, ((1, 128), I32))
    rest = [((slots, 8), I32), ((slots,), I32), toks] + (
        [((), I32)] if phase == "prefill" else [])
    args = [put(params)] + pools + [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in rest]
    make = make_paged_decode_step if phase == "decode" \
        else make_paged_prefill_step
    step = dispatch.pin_backend(make(cfg, policy), "pallas")
    text = jax.jit(step, donate_argnums=POOL_ARGNUMS).lower(
        *args).compile().as_text()

    # the pools are the parameters after the weights' leaves, and alias the
    # step's outputs 2 and 3
    first = len(jax.tree_util.tree_leaves(params))
    aliases = re.search(r"input_output_alias=\{ (.*?) \}", text).group(1)
    for i, e in enumerate(entries):
        shape = ",".join(map(str, (L, N, bs) + e))
        assert re.search(rf"= f32\[{shape}\]\S* parameter\({first + i}\)",
                         text)
        assert re.search(rf"\{{{2 + i}\}}: \({first + i}, \{{\}}",
                         aliases), aliases

    pool_shapes = set()
    for e in entries:
        tail = ",".join(map(str, (bs,) + e))
        pool_shapes |= {f"{L},{N},{tail}", f"{L * N},{tail}", f"{N},{tail}",
                        f"1,{N},{tail}"}
    insts = _instructions(text)
    roots = {comp: op for comp, _, _, op, root in insts if root}
    moves, relayouts = [], []
    for _, name, result, op, _ in insts:
        if not pool_shapes & set(re.findall(r"f32\[([\d,]+)\]", result)):
            continue
        if op == "fusion":   # allowed where it writes rows: a scatter
            called = re.search(rf"%{re.escape(name)} = .*calls=%([\w.-]+)",
                               text).group(1)
            op = f"fusion of {roots[called]}"
            if roots[called] == "scatter":
                continue
        if op == "copy" and result.startswith(f"f32[{L},{N},{bs},64]{{"):
            relayouts.append(result)
        elif op not in POOL_OPS:
            moves.append((name, op))
    assert not moves, moves
    assert len(relayouts) == (2 if cfg.mla is not None else 0), relayouts
    assert len(set(relayouts)) == len(relayouts)   # there and back

    kernel = "mp_latent_paged_attention" if cfg.mla is not None \
        else "mp_paged_attention"
    calls = [line for line in text.splitlines()
             if re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(", line)]
    assert len(calls) == (L if phase == "decode" else 0)
    flat = [f"f32[{L * N},{bs},{','.join(map(str, e))}]" for e in entries]
    for line in calls:
        assert all(f in line for f in flat), line[:300]
    if cfg.mla is not None:
        fam = [re.compile(p) for p in json.loads(
            (FAMILIES / "mla_attention.json").read_text())["patterns"]]
        assert all(any(p.search(x) for p in fam) for x in calls)

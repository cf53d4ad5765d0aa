"""Headline benchmark: training goodput under an injected preemption with
Flash Checkpoint, plus a compute-bound MFU probe.

Goodput (the reference's headline metric — README.md:54-55 lifts goodput
69%->95% on GLM-65B): train a GPT-2-family model, flash-save
asynchronously (shm staging off the critical path —
``save_to_memory(block=False)``), inject one preemption mid-run (discard
all device state, restore from the in-memory checkpoint), keep training.
Goodput = pure-step time fraction of total wall time. The scenario is
~100x harsher than the reference's (one preemption per ~3 minutes instead
of per hours), so hitting the same 95% here is a stricter bar. The model
size is picked from the measured host<->device bandwidth (``_pick_config``).

MFU (BASELINE.md rows 9-10: ATorch Llama2-7B hits 204.7 TFLOPs/65.6% HFU
on A100): the headline probe trains GPT-2 XL (1.557B) end to end — bf16,
flash attention, fused 8-bit Adam, gradient accumulation — and reports
the fraction of chip peak (``run_mfu_big``; no remat, so MFU == HFU,
vs the reference's HFU which counts remat recompute). A small-model
probe (124M) rides along for round-over-round comparability, and a
staging microbench reports GB-scale shm/disk throughput so the
tiny-model goodput number has a measured extrapolation.

Prints ONE JSON line: {"metric","value","unit","vs_baseline","mfu_pct",
"stage_MBps","persist_MBps",...breakdown}.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time
from dataclasses import replace
from typing import Optional

import numpy as np

REF_GOODPUT_PCT = 95.0  # reference's published goodput (README.md:54-55)

# every bench artifact (trace dumps, merged timelines, flight bundles
# the forensics leg provokes) lands under one dir instead of littering
# the repo root; override per-run with DLROVER_TPU_BENCH_ARTIFACTS
ENV_BENCH_ARTIFACTS = "DLROVER_TPU_BENCH_ARTIFACTS"
DEFAULT_BENCH_ARTIFACTS = "bench_artifacts"


def artifacts_dir() -> str:
    d = os.getenv(ENV_BENCH_ARTIFACTS, DEFAULT_BENCH_ARTIFACTS)
    os.makedirs(d, exist_ok=True)
    return d


def _chip_peak_tflops(device) -> float | None:
    from dlrover_tpu.accel.profiler import chip_peak_tflops

    return chip_peak_tflops(device)


def _probe_link_bw(jax) -> float:
    """Device->host bandwidth in bytes/s (8 MB probe). Each timing uses a
    fresh device array — jax.Array caches its host copy after the first
    np.asarray, which would make a repeat read look infinitely fast."""
    import jax.numpy as jnp

    make = jax.jit(lambda s: jnp.full((2 * 1024 * 1024,), s, jnp.float32))
    jax.block_until_ready(make(0.0))  # compile + path warmup
    np.asarray(make(1.0))
    x = make(2.0)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    np.asarray(x)
    dt = max(time.perf_counter() - t0, 1e-4)
    return 8 * 1024 * 1024 / dt


def _pick_config(jax, bw: float):
    """Choose the goodput model so the full ckpt state (params + adam m/v,
    fp32 => 12 B/param) crosses the link in ~1.2 s."""
    from dlrover_tpu.models import gpt2_small, tiny

    param_budget = bw * 1.2 / 12
    if param_budget >= 120e6:
        return gpt2_small(), "gpt2_small(124M)", (8, 1024)
    if param_budget >= 25e6:
        return (
            replace(
                gpt2_small(), num_layers=6, model_dim=512, num_heads=8,
                max_seq_len=512,
            ),
            "gpt2_mini(33M)",
            (8, 512),
        )
    if param_budget >= 4e6:
        return (
            replace(
                gpt2_small(), vocab_size=8192, num_layers=4, model_dim=256,
                num_heads=8, max_seq_len=512,
            ),
            "gpt2_nano(5M)",
            (8, 512),
        )
    if param_budget >= 1e6:
        return (
            replace(
                gpt2_small(), vocab_size=4096, num_layers=3, model_dim=128,
                num_heads=4, max_seq_len=256,
            ),
            "gpt2_micro(1.2M)",
            (8, 256),
        )
    return tiny(), "tiny", (8, 64)


def _model_flops_per_step(cfg, batch: int, seq: int, n_params: int) -> float:
    """Fwd+bwd FLOPs: 6*P*tokens plus the attention term the 6P rule
    misses (12*L*B*H*T^2*head_dim fwd+bwd halves -> causal ~/2)."""
    tokens = batch * seq
    dense = 6.0 * n_params * tokens
    attn = 12.0 * cfg.num_layers * batch * seq * seq * cfg.model_dim / 2
    return dense + attn


def _make_restore_template(jax, cfg, mesh, tx):
    """Precompiled sharded-zeros TrainState builder — what a restarted
    worker compiles during bring-up, before it loads. Shared by both
    goodput probes so template-sharding fixes cannot diverge."""
    import jax.numpy as jnp

    from dlrover_tpu.models import TrainState, init_params
    from dlrover_tpu.models.train import state_shardings

    sh = state_shardings(cfg, mesh, tx)
    params_shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )

    def _zeros():
        p = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), params_shapes
        )
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p)
        )

    make_template = jax.jit(
        _zeros,
        out_shardings=TrainState(
            step=sh.step, params=sh.params, opt_state=sh.opt_state
        ),
    )
    jax.block_until_ready(make_template())
    return make_template


def run_goodput(jax, results: dict) -> bool:
    import optax

    from dlrover_tpu.ckpt.engine import CheckpointEngine
    from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
    from dlrover_tpu.models import (
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    on_accel = jax.devices()[0].platform != "cpu"
    if not on_accel:
        # CPU smoke run: the link probe would measure memcpy and pick a
        # model one core cannot train
        bw = 0.0
        from dlrover_tpu.models import tiny

        cfg, model_name, (batch, seq) = tiny(), "tiny(cpu)", (8, 64)
    else:
        bw = _probe_link_bw(jax)
        cfg, model_name, (batch, seq) = _pick_config(jax, bw)
    cfg = replace(cfg, max_seq_len=seq)

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshConfig(dp=n_dev))
    tx = optax.adamw(3e-4, weight_decay=0.01)
    state, _ = init_sharded_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    # async staging reads state buffers after the step returns -> no donate
    step_fn = build_train_step(cfg, mesh, tx, donate=False)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    data = shard_batch({"x": tokens, "y": tokens}, mesh)

    # flash checkpoint plumbing (in-process saver = the agent's daemon)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    AsyncCheckpointSaver.reset()
    AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    engine = CheckpointEngine()

    try:
        return _goodput_body(
            jax, results, engine, ckpt_dir, cfg, model_name, mesh, tx,
            state, step_fn, data, batch, seq, bw, on_accel, n_dev,
        )
    finally:
        # clean shutdown on EVERY path: join staging threads BEFORE the
        # runtime can start tearing down (a daemon thread must not be
        # mid-D2H at exit), then close the saver (drains + unlinks shm)
        engine.close()
        AsyncCheckpointSaver.reset()


def _goodput_body(
    jax, results, engine, ckpt_dir, cfg, model_name, mesh, tx,
    state, step_fn, data, batch, seq, bw, on_accel, n_dev,
) -> bool:
    make_template = _make_restore_template(jax, cfg, mesh, tx)
    sync_state = _make_hard_sync(jax, make_template())

    # warmup/compile + step-time calibration
    state, _ = step_fn(state, data["x"], data["y"])
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(3):
        state, _ = step_fn(state, data["x"], data["y"])
        jax.block_until_ready(state.params)
    cal_step = (time.perf_counter() - t0) / 3
    # ~180s of pure compute on an accelerator (8s on a CPU smoke run);
    # preempt once in the middle — still ~100x more preemption-dense than
    # the reference scenario this imitates
    budget, cap = (180.0, 4000) if on_accel else (8.0, 60)
    total_steps = int(min(cap, max(20, budget / max(cal_step, 1e-3))))
    save_every = max(2, total_steps // 8)
    preempt_at = total_steps // 2 + 1

    t_bench0 = time.perf_counter()
    step_time = 0.0
    save_block = []
    restore_s = 0.0
    preempted = False
    done = 0
    # if the first commit lags, keep training (up to 3x the budget) until
    # the preemption scenario can actually run
    hard_cap = total_steps * 3
    while done < total_steps or (not preempted and done < hard_cap):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, data["x"], data["y"])
        float(metrics["loss"])  # honest sync: block_until_ready can
        step_time += time.perf_counter() - t0  # return early here
        done += 1

        if done % save_every == 0 and done < total_steps:
            t0 = time.perf_counter()
            engine.save_to_memory(done, state, ckpt_dir, block=False)
            save_block.append(time.perf_counter() - t0)

        if (
            done >= preempt_at
            and not preempted
            and engine.latest_step(ckpt_dir) >= 0
        ):
            # preempting before any commit would just mean restart-from-
            # scratch; the interesting path is restore-from-checkpoint
            preempted = True
            del state
            t0 = time.perf_counter()
            template = make_template()
            step0, state = engine.load(template, ckpt_dir)
            if state is None or step0 < 0:
                return False  # cleanup runs in run_goodput's finally
            sync_state(state)
            restore_s = time.perf_counter() - t0
            done = step0

    wall = time.perf_counter() - t_bench0
    # the shared definition (obs/goodput.py) — bench legs measure their
    # own productive/wall seconds (cross-process windows no single
    # tracer sees) but must divide through the same formula the
    # continuous ledger exports, or the two "goodput"s drift
    from dlrover_tpu.obs.goodput import compute_goodput_pct

    goodput = compute_goodput_pct(step_time, wall)

    results.update(
        {
            "metric": "goodput_pct_preempt_flashckpt_gpt2",
            "value": round(goodput, 2),
            "unit": "%",
            "vs_baseline": round(goodput / REF_GOODPUT_PCT, 4),
            "save_block_ms_mean": round(
                1e3 * float(np.mean(save_block)), 2
            ),
            "restore_s": round(restore_s, 3),
            "step_s": round(step_time / max(done, 1), 4),
            "steps": done,
            "preempted": preempted,
            "model": model_name,
            "d2h_link_MBps": round(bw / 1e6, 1),
            "devices": n_dev,
            "platform": jax.devices()[0].platform,
        }
    )
    return True


def _make_hard_sync(jax, spec):
    """Build a PRE-COMPILED every-buffer reduction for ``spec``-shaped
    trees: calling it fetches a 4-byte scalar that depends on every
    buffer, so a timing closed with it covers the transfers and the
    execution and not only their dispatch. Compiling here (not inside
    the timed region) keeps the measuring instrument out of the
    measurement."""
    import jax.numpy as jnp

    def _total(t):
        acc = jnp.float32(0)
        for leaf in jax.tree_util.tree_leaves(t):
            acc = acc + jnp.sum(leaf.astype(jnp.float32))
        return acc

    compiled = jax.jit(_total).lower(spec).compile()
    return lambda tree: float(compiled(tree))




def run_sp_compare(jax, results: dict):
    """Ring vs Ulysses sequence parallelism with the KERNEL STRATEGY
    HELD CONSTANT: each scheme's per-device compute is
    timed both ways — "fused" = [1024x1024] fused-kernel tiles + online
    merges (``flash_attention_fwd_chunked``; ring's hops get the same
    driver so T/sp > 1024 chunks also tile), "stream" = the block-tiled
    streaming kernel — at seq 4096 AND 8192, sp=4, bf16.

    One chip cannot run the sp=4 collectives, so this times
    exactly the part that differs per device (ring's ppermute overlaps
    compute; Ulysses' two all-to-alls move act_bytes/sp per device over
    ICI — noted analytically). The dryrun proves both schemes'
    collectives compile+run on the 8-way virtual mesh. ``sp_scheme``
    selection reads this table: rows are written as
    ``sp_{scheme}_{kernel}_ms_{T}`` plus ``sp_recommended_{T}``.
    """
    import functools

    import jax.numpy as jnp

    from dlrover_tpu.ops.flash_attention import (
        flash_attention_fwd,
        flash_attention_fwd_chunked,
        merge_partials,
    )

    if jax.devices()[0].platform == "cpu":
        return
    B, H, D = 2, 16, 128
    sp = 4
    rng = np.random.default_rng(3)

    def mk(h, t):
        return (
            jnp.asarray(rng.normal(size=(B, t, h, D)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(B, t, h, D)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(B, t, h, D)), jnp.bfloat16),
        )

    def make_ring(T, fused):
        chunk = min(1024, T // sp)

        @functools.partial(jax.jit, static_argnums=(3,))
        def ring_device(q, k, v, iters):
            # one device's work per step: sp hop calls, q [T/sp] local,
            # each hop's k/v chunk [T/sp], ONLINE-MERGED across hops
            # exactly as parallel/ring_attention.py does (the last
            # rank's causal bottleneck hops)
            def one(acc, _):
                o_acc, lse_acc = None, None
                for hop in range(sp):
                    if fused:
                        o_h, lse_h = flash_attention_fwd_chunked(
                            q, k, v, causal=True,
                            q_offset=(sp - 1) * (T // sp),
                            k_offset=hop * (T // sp),
                            chunk=chunk,
                        )
                    else:
                        o_h, lse_h = flash_attention_fwd(
                            q, k, v, causal=True,
                            q_offset=(sp - 1) * (T // sp),
                            k_offset=hop * (T // sp),
                            allow_fused=False,
                        )
                    o_h = o_h.astype(jnp.float32)
                    if o_acc is None:
                        o_acc, lse_acc = o_h, lse_h
                    else:
                        o_acc, lse_acc = merge_partials(
                            o_acc, lse_acc, o_h, lse_h
                        )
                return acc + o_acc, None

            acc0 = jnp.zeros((B, T // sp, H, D), jnp.float32)
            out, _ = jax.lax.scan(one, acc0, jnp.arange(iters))
            return out[0, 0, 0, 0]

        return ring_device

    def make_ulysses(T, fused):
        @functools.partial(jax.jit, static_argnums=(3,))
        def ulysses_device(q, k, v, iters):
            # one device's work per step: full sequence, H/sp heads
            def one(acc, _):
                if fused:
                    o, _ = flash_attention_fwd_chunked(
                        q, k, v, causal=True, chunk=1024
                    )
                else:
                    o, _ = flash_attention_fwd(
                        q, k, v, causal=True, allow_fused=False
                    )
                return acc + o.astype(jnp.float32), None

            acc0 = jnp.zeros((B, T, H // sp, D), jnp.float32)
            out, _ = jax.lax.scan(one, acc0, jnp.arange(iters))
            return out[0, 0, 0, 0]

        return ulysses_device

    iters = 20
    for T in (4096, 8192):
        qr, kr, vr = mk(H, T // sp)
        qu, ku, vu = mk(H // sp, T)
        best = {}
        for scheme, maker, args in (
            ("ring", make_ring, (qr, kr, vr)),
            ("ulysses", make_ulysses, (qu, ku, vu)),
        ):
            for kernel, fused in (("fused", True), ("stream", False)):
                fn = maker(T, fused)
                # warm up the SAME static-iters executable the timer
                # runs (iters is static — another value recompiles)
                float(fn(*args, iters))
                t0 = time.perf_counter()
                float(fn(*args, iters))
                ms = round((time.perf_counter() - t0) / iters * 1e3, 2)
                results[f"sp_{scheme}_{kernel}_ms_{T}"] = ms
                best[(scheme, kernel)] = ms
        # same tie rule (and the same constant) as
        # parallel/sp_select.py: ulysses must WIN by margin (its
        # all-to-alls don't overlap; ring's ppermute does) — run-to-run
        # variance otherwise flips a ~1% difference
        from dlrover_tpu.parallel.sp_select import _TIE_MARGIN

        ring_ms = min(best[("ring", "fused")], best[("ring", "stream")])
        uly_ms = min(
            best[("ulysses", "fused")], best[("ulysses", "stream")]
        )
        results[f"sp_recommended_{T}"] = (
            "ulysses" if uly_ms < ring_ms * _TIE_MARGIN else "ring"
        )
    # best kernel per scheme
    results["sp_ring_attn_ms"] = min(
        results["sp_ring_fused_ms_4096"], results["sp_ring_stream_ms_4096"]
    )
    results["sp_ulysses_attn_ms"] = min(
        results["sp_ulysses_fused_ms_4096"],
        results["sp_ulysses_stream_ms_4096"],
    )
    results["sp_compare_note"] = (
        f"per-device flash-attention compute, sp={sp}, H={H}, D={D}, "
        "bf16, kernel strategy held constant per row: fused = "
        "1024x1024 fused tiles + online merges (both schemes), stream "
        "= block-tiled streaming kernel (both schemes). Ring rows "
        "include its per-hop merge cost; ulysses pays +2 all-to-alls "
        "(act_bytes/sp per device over ICI) not timeable on one chip"
    )


def run_mfu_big(jax, results: dict, carry: Optional[dict] = None):
    """Big-model MFU probe: GPT-2 XL (1.557B params) FULL training
    update on one chip — bf16 params/activations, flash attention, the
    repo's fused 8-bit Adam, gradient accumulation.

    Design notes (numbers from a run of an earlier tree on one v5e
    chip; not measured on today's code):
    - HBM budget: params(bf16, 3.1 GB) + 8-bit Adam state(~3.3 GB) +
      grads(bf16, 3.1 GB) + activations cap the microbatch at 4x512
      tokens WITHOUT remat. fwd+bwd alone runs at ~56-57% of peak at
      that shape — the chip's ceiling for this model (D=1600 pads the
      128-lane tiles; the 50k-vocab head is ~61% efficient).
    - the optimizer pass is param-sized HBM traffic (~170 ms in tree
      form); gradient accumulation (K microbatches per update — the
      standard large-global-batch recipe; global batch here is
      K*4*512 = 131k tokens) amortizes it to noise. Accumulation runs
      HOST-side as three small programs; build_train_step(grad_accum=K)
      is the in-framework path (never compiled for the chip at this
      size: see PERF.md).
    - a scalar readback per UPDATE syncs the dispatch queue (the async
      frees of donated buffers otherwise race the next update's
      allocations at this HBM occupancy) and costs ~RTT/K per
      microbatch.

    vs BASELINE.md row 9 (Llama2-7B, 65.6% **HFU** with full activation
    checkpointing on A100): HFU counts the remat recompute (~4/3x), so
    65.6% HFU ~= 49.2% MFU. This probe runs NO remat: its MFU == HFU.
    """
    import functools

    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import gpt2_xl, init_params
    from dlrover_tpu.models.transformer import loss_fn
    from dlrover_tpu.ops.quantized_optim import adamw_8bit_flat

    if jax.devices()[0].platform == "cpu":
        results["mfu_pct"] = None
        return

    mb, seq, K = 4, 512, 64
    cfg = replace(
        gpt2_xl(), max_seq_len=seq, dtype="bfloat16",
        param_dtype="bfloat16",
    )
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    # group-packed flat 8-bit Adam: same measured speed as the tree
    # form, ~40x fewer HLO ops (docs/performance.md trace breakdown)
    tx = adamw_8bit_flat(3e-4)
    opt = jax.jit(tx.init)(params)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def grad_acc(p, g_acc, x):
        loss, g = jax.value_and_grad(lambda q: loss_fn(q, x, x, cfg))(p)
        return jax.tree_util.tree_map(jnp.add, g_acc, g), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(p, o, g_sum):
        g = jax.tree_util.tree_map(lambda a: a / K, g_sum)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o

    zeros_g = jax.jit(
        lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)
    )
    x = jax.jit(
        lambda k: jax.random.randint(
            k, (mb, seq), 0, cfg.vocab_size, jnp.int32
        )
    )(jax.random.PRNGKey(1))
    jax.block_until_ready(x)

    def one_update(p, o):
        g = zeros_g(p)
        loss = None
        for _ in range(K):
            g, loss = grad_acc(p, g, x)
        p, o = apply(p, o, g)
        float(loss)  # per-update sync (see docstring)
        return p, o

    params, opt = one_update(params, opt)  # compile + warmup
    steps = 3
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt = one_update(params, opt)
    dt = (time.perf_counter() - t0) / steps

    flops = K * _model_flops_per_step(cfg, mb, seq, n_params)
    tflops = flops / dt / 1e12
    peak = _chip_peak_tflops(jax.devices()[0])
    results["mfu_pct"] = (
        round(100.0 * tflops / peak, 1) if peak else None
    )
    results["model_tflops"] = round(tflops, 1)
    results["mfu_model"] = (
        f"gpt2_xl(1.557B) bf16 8bit-adam grad_accum{K} "
        f"mb{mb} seq{seq} (global batch {K * mb * seq} tok)"
    )
    results["mfu_update_s"] = round(dt, 3)
    results["mfu_note"] = (
        "full training update incl. fused 8-bit Adam, no remat (MFU==HFU"
        "); ref 65.6% HFU w/ full remat ~= 49.2% MFU-equivalent"
    )

    # optimizer-pass share, measured honestly: queued donated state
    # (grads NOT donated so one buffer serves every iteration) with ONE
    # scalar readback THROUGH the dependency chain (an unforced
    # block_until_ready returns early on this runtime)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply_probe(p, o, g_sum):
        g = jax.tree_util.tree_map(lambda a: a / K, g_sum)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o

    g = zeros_g(params)
    opt_iters = 10
    p3, o3 = apply_probe(params, opt, g)
    # force the warmup's device execution BEFORE the timer (pitfall 1)
    float(
        jax.tree_util.tree_leaves(p3)[0].reshape(-1)[0].astype("float32")
    )
    t0 = time.perf_counter()
    for _ in range(opt_iters):
        p3, o3 = apply_probe(p3, o3, g)
    float(
        jax.tree_util.tree_leaves(p3)[0].reshape(-1)[0].astype("float32")
    )
    results["opt_pass_ms"] = round(
        (time.perf_counter() - t0) / opt_iters * 1000, 1
    )
    if carry is not None:
        # hand the live 1.5B state to the flash-ckpt probe (params were
        # donated through apply_probe — p3/o3 are the current buffers)
        carry["state"] = {"params": p3, "opt_state": o3}
        carry["cfg"] = cfg


def run_staging_bench(jax, results: dict):
    """Flash-checkpoint staging throughput at GB scale.

    The goodput scenario's model is sized from the measured D2H
    bandwidth and may be small, so these two numbers cover GB scale:

    - ``stage_MBps``: device->host->shared-memory, through the SAME
      primitives the engine's staging thread uses (device_get + shm
      buffer copy), sized to ~10 s on the measured link;
    - ``persist_MBps``: shm->disk (the agent saver's leg), measured at
      1 GB — host-local, so it runs at real scale regardless of the
      device link.
    """
    from multiprocessing import shared_memory

    # -- persist leg: shm -> disk at 1 GB (no device involved)
    size = 1 << 30
    shm = shared_memory.SharedMemory(create=True, size=size)
    try:
        shm.buf[:] = b"\x7f" * size
        tmpdir = tempfile.mkdtemp(prefix="bench_persist_")
        path = os.path.join(tmpdir, "blob")
        t0 = time.perf_counter()
        with open(path, "wb") as f:
            f.write(shm.buf)
            f.flush()
            os.fsync(f.fileno())
        dt = time.perf_counter() - t0
        results["persist_MBps"] = round(size / dt / 1e6, 1)
        results["persist_GB"] = round(size / 1e9, 2)
        os.unlink(path)
        os.rmdir(tmpdir)
    finally:
        shm.close()
        shm.unlink()

    # -- stage leg: device -> shm, sized to ~10 s on this link
    bw = results.get("d2h_link_MBps", 0.0) * 1e6
    if not bw or jax.devices()[0].platform == "cpu":
        results["stage_MBps"] = None
        return
    import jax.numpy as jnp

    stage_bytes = int(min(max(bw * 10, 64 << 20), 8 << 30))
    n = stage_bytes // 4
    make = jax.jit(lambda s: jnp.full((n,), s, jnp.float32))
    jax.block_until_ready(make(1.0))
    shm = shared_memory.SharedMemory(create=True, size=stage_bytes)
    try:
        x = make(2.0)
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        host = np.asarray(x)  # the engine's device_get leg
        # the engine's shm leg is a zero-extra-copy view assignment
        # (ckpt/shm_handler.py) — tobytes() would double host memory
        # and the measured time
        np.frombuffer(shm.buf, np.uint8, stage_bytes)[:] = host.view(
            np.uint8
        ).ravel()
        dt = time.perf_counter() - t0
        results["stage_MBps"] = round(stage_bytes / dt / 1e6, 1)
        results["stage_GB"] = round(stage_bytes / 1e9, 3)
    finally:
        shm.close()
        shm.unlink()


def run_coworker_feed(results: dict):
    """Cross-host coworker data plane throughput: a
    DataNodeServer streaming batches over TCP into a trainer-side
    RemoteBatchFeeder (fetcher processes -> local shm ring -> consumer).
    Loopback TCP on this host — an upper bound for the network leg, an
    honest end-to-end number for serialize + socket + decode + shm-ring
    machinery."""
    from dlrover_tpu.data.remote_feed import (
        DataNodeServer,
        RemoteBatchFeeder,
    )

    n_batches, mb = 16, 16
    batch = {
        "x": np.arange(mb << 18, dtype=np.int32).reshape(-1, 1024),
        "y": np.ones((mb << 8,), np.float32),
    }
    nbytes = sum(a.nbytes for a in batch.values())

    def gen():
        for _ in range(n_batches):
            yield batch

    server = feeder = None
    try:
        server = DataNodeServer(gen(), host="127.0.0.1")
        feeder = RemoteBatchFeeder(
            [f"127.0.0.1:{server.port}"], fetchers_per_node=2,
            slot_bytes=(mb + 4) << 20, name="bench_feed",
        )
        t0 = time.perf_counter()
        got = sum(1 for _ in feeder)
        dt = time.perf_counter() - t0
        assert got == n_batches, got
        results["coworker_feed_MBps"] = round(
            n_batches * nbytes / dt / 1e6, 1
        )
        results["coworker_feed_note"] = (
            f"{n_batches} x {nbytes >> 20} MB batches, TCP data node -> "
            "2 fetcher procs -> shm ring -> trainer iterator, loopback"
        )
    finally:
        if feeder is not None:
            feeder.close()
        if server is not None:
            server.close()


def run_pipeline_bench(jax, results: dict, smoke: bool = False):
    """Overlapped host↔device pipeline probes (two legs, shared keys
    with the ``--smoke`` CPU path so regressions fail loudly in CI):

    - **feed + prefetch**: a producer with real host cost (batch
      synthesis) feeds a device consumer, measured serial
      (``feed_MBps_prefetch_off``) then through the double-buffered
      ``DevicePrefetcher`` (``feed_MBps_prefetch_on``);
      ``prefetch_overlap_pct`` = batches already device-placed when the
      consumer asked.
    - **chunked staging**: the same state is staged to shm once as a
      single synchronous drain (``stage_sync_block_ms``) and once
      chunked between fake train steps; ``stage_amortized_block_ms`` is
      the mean per-step critical-path cost of ``advance()`` — the
      number that must sit far below the single-drain block.
    """
    import jax.numpy as jnp

    from dlrover_tpu.accel.profiler import PipelineStats
    from dlrover_tpu.data.prefetch import DevicePrefetcher

    on_cpu = jax.devices()[0].platform == "cpu"
    small = smoke or on_cpu

    # -- feed leg ------------------------------------------------------
    n_batches = 8 if small else 24
    rows = 256 if small else 2048
    cols = 1024
    nbytes = rows * cols * 4

    def produce():
        rng = np.random.default_rng(0)
        for _ in range(n_batches):
            # the host cost a real feed pays (synthesis stands in for
            # decode/augment); this is what the prefetcher hides
            yield rng.standard_normal((rows, cols)).astype(np.float32)

    w = jnp.asarray(
        np.random.default_rng(1).standard_normal((cols, cols)),
        jnp.float32,
    )
    consume = jax.jit(lambda x, w: jnp.sum(jnp.tanh(x @ w)))
    # warm the compile out of both timed loops
    float(consume(jax.device_put(next(produce())), w))

    t0 = time.perf_counter()
    for b in produce():
        float(consume(jax.device_put(b), w))
    t_off = time.perf_counter() - t0

    stats = PipelineStats()
    pf = DevicePrefetcher(produce(), depth=2, stats=stats)
    try:
        t0 = time.perf_counter()
        for b in pf:
            float(consume(b, w))
        t_on = time.perf_counter() - t0
    finally:
        pf.close()
    results["feed_MBps_prefetch_off"] = round(
        n_batches * nbytes / t_off / 1e6, 1
    )
    results["feed_MBps_prefetch_on"] = round(
        n_batches * nbytes / t_on / 1e6, 1
    )
    results["prefetch_overlap_pct"] = stats.prefetch_overlap_pct

    # -- staging leg ---------------------------------------------------
    from dlrover_tpu.ckpt.engine import CheckpointEngine
    from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver

    state_mb = 32 if small else 256
    n_arr = 8
    make = jax.jit(
        lambda k: jax.random.normal(
            k, ((state_mb << 20) // 4 // n_arr,), jnp.float32
        )
    )
    state = {
        f"w{i}": make(jax.random.PRNGKey(i)) for i in range(n_arr)
    }
    jax.block_until_ready(state)
    step_w = jnp.zeros((512, 512), jnp.float32) + 0.001
    fake_step = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())
    float(fake_step(step_w))  # compile

    ckpt_dir = tempfile.mkdtemp(prefix="bench_pipe_ckpt_")
    AsyncCheckpointSaver.reset()
    AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    engine = CheckpointEngine()
    try:
        t0 = time.perf_counter()
        if not engine.save_to_memory(1, state, ckpt_dir, block=True):
            results["pipeline_stage_error"] = "sync stage skipped"
            return
        results["stage_sync_block_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2
        )
        t0 = time.perf_counter()
        while engine.latest_step(ckpt_dir) < 1:
            time.sleep(0.1)
            if time.perf_counter() - t0 > 300:
                results["pipeline_stage_error"] = "sync never committed"
                return
        stager = engine.begin_chunked_save(
            2, state, ckpt_dir,
            chunk_bytes=(1 << 20) if small else (8 << 20),
        )
        if stager is None:
            results["pipeline_stage_error"] = "chunked stage skipped"
            return
        blocks = []
        steps = 0
        while not stager.done and steps < 10000:
            float(fake_step(step_w))  # the overlapped compute
            t0 = time.perf_counter()
            stager.advance(budget_s=0.002)
            blocks.append(time.perf_counter() - t0)
            steps += 1
        t0 = time.perf_counter()
        stager.commit()
        commit_ms = (time.perf_counter() - t0) * 1e3
        results["stage_amortized_block_ms"] = round(
            1e3 * float(np.mean(blocks)), 3
        )
        results["stage_amortized_block_ms_max"] = round(
            1e3 * float(np.max(blocks)), 3
        )
        results["stage_chunked_steps"] = steps
        results["stage_chunked_commit_ms"] = round(commit_ms, 2)
        results["stage_chunked_state_MB"] = state_mb
        results["pipeline_note"] = (
            "feed: synthesis-cost producer -> device consumer, serial "
            "vs double-buffered prefetch; staging: same state staged "
            "as one synchronous drain vs fixed-size chunks interleaved "
            "between steps (2 ms/step budget, commit is the only "
            "barrier)"
        )
    finally:
        engine.close()
        AsyncCheckpointSaver.reset()


def run_resize_bench(jax, results: dict, smoke: bool = False):
    """Elastic-resize fast path: cold vs warm resize downtime.

    The scenario (CPU smoke runs it on fake devices, mesh 4→2→4): an
    ``ElasticTrainer`` trains on 4 devices — its first step lands the
    4-mesh executable in the AOT compile cache — then resizes to 2
    (cold: that mesh was never compiled; the downtime window pays the
    full XLA compile on top of the on-device reshard) and back to 4
    (warm: cache hit — the window is reshard + bookkeeping only).
    Keys:

    - ``resize_downtime_cold_ms`` / ``resize_downtime_warm_ms`` — wall
      time training is stopped per resize; the fast path's contract is
      warm ≤ 50% of cold even at toy scale (at real scale compile is
      minutes and the ratio collapses further);
    - ``compile_cache_hit_pct`` — over all AOT lookups; the second
      resize of the run MUST make this > 0 or the warm path regressed
      (``--smoke`` exits nonzero on that);
    - ``reshard_bytes_device`` vs ``reshard_bytes_host`` — state bytes
      remapped on device vs fallen back to the host restore (all-device
      here: every source survives an in-process resize).
    """
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    devs = list(jax.devices())
    if len(devs) < 4:
        results["resize_error"] = (
            f"resize bench needs >= 4 devices, have {len(devs)}"
        )
        return

    class _Tokens:
        def __init__(self, n=128, seq=32, vocab=256):
            rng = np.random.default_rng(0)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    trainer = ElasticTrainer(
        # smoke: 1 layer — the scenario gates cache/reshard machinery,
        # and a smaller program keeps the tier-1 gate cheap; the full
        # bench pays for the complete test model
        model_cfg=tiny(num_layers=1) if smoke else tiny(),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            report_metrics=False,
            log_interval=1000,
            prefetch=2,
            # the warm window must not hide a lazy donating-twin
            # compile inside the first post-resize step
            donation_aware=False,
            speculative_compile=False,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=4), dtype="float32"),
        devices=devs[:4],
    )
    try:
        trainer.train(num_steps=2)
        cold = trainer.resize(2)
        trainer.train(num_steps=4)
        warm = trainer.resize(4)
        trainer.train(num_steps=6)
        stats = trainer.pipeline_stats
        results["resize_downtime_cold_ms"] = round(
            cold["downtime_ms"], 2
        )
        results["resize_downtime_warm_ms"] = round(
            warm["downtime_ms"], 2
        )
        results["compile_cache_hit_pct"] = stats.compile_cache_hit_pct
        results["resize_second_cache_hit"] = bool(
            warm["compile_cache_hit"]
        )
        results["reshard_bytes_device"] = stats.reshard_bytes_device
        results["reshard_bytes_host"] = stats.reshard_bytes_host
        results["reshard_bytes_device_vs_host"] = [
            stats.reshard_bytes_device,
            stats.reshard_bytes_host,
        ]
        results["resize_note"] = (
            "mesh dp4 -> dp2 (cold compile) -> dp4 (AOT cache hit), "
            "live state remapped on device, prefetcher closed+rewound "
            "before each reshard"
        )
    finally:
        trainer.close()

    # -- warm pp resize (ISSUE 13 satellite): dp2 x pp2 -> dp4 x pp2
    # and back, at the reshard + AOT-cache level (the trainer's resize
    # fast path is pp=1 by contract; the pipeline world's warm resize
    # is reshard_state over the stage-stacked tree + a compile-cache
    # hit on the explicit pp step)
    try:
        import time as _time

        import optax

        from dlrover_tpu.accel.compile_cache import (
            CompileCache,
            fingerprint,
            mesh_signature,
        )
        from dlrover_tpu.models.train import TrainState
        from dlrover_tpu.models.transformer import init_params
        from dlrover_tpu.parallel.mesh import build_mesh
        from dlrover_tpu.parallel.pipeline import (
            build_pipeline_train_step,
            pipeline_state_shardings,
            stack_pipeline_params,
        )
        from dlrover_tpu.ckpt.reshard import reshard_state

        if len(devs) < 8:
            raise RuntimeError("pp resize leg needs 8 devices")
        cfg = tiny(num_layers=2)
        cfg = replace(cfg, dtype="float32", param_dtype="float32")
        tx = optax.adamw(1e-2)
        rng = np.random.default_rng(0)
        x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
        import jax.numpy as jnp

        xj = jnp.asarray(x)
        cache = CompileCache()
        params0 = init_params(jax.random.PRNGKey(0), cfg)

        def world(mc, n):
            mesh = build_mesh(mc, devices=devs[:n])
            sh = pipeline_state_shardings(cfg, mesh, tx)
            step = build_pipeline_train_step(
                cfg, mesh, tx, 2, donate=False, schedule="gpipe",
                comm_overlap=True, grad_bucket_mb=1,
            )
            return mesh, sh, step

        def spec_of(sh, shapes):
            return jax.tree_util.tree_map(
                lambda s, shd: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=shd
                ),
                shapes,
                sh,
            )

        mc_a = MeshConfig(pp=2, dp=2)
        mc_b = MeshConfig(pp=2, dp=4)
        mesh_a, sh_a, step_a = world(mc_a, 4)
        stacked = jax.device_put(
            stack_pipeline_params(params0, 2), sh_a.params
        )
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=stacked,
            opt_state=jax.device_put(tx.init(stacked), sh_a.opt_state),
        )

        def compiled(step, mesh, state):
            key = fingerprint(
                "pp_step", mesh_signature(mesh), repr(cfg)
            )
            fn, _ = cache.get_or_compile(
                key, lambda: step.lower(state, xj, xj).compile()
            )
            return fn

        fn_a = compiled(step_a, mesh_a, state)
        state, _ = fn_a(state, xj, xj)  # prime world A
        jax.block_until_ready(state.params)

        def move(state, mc, n):
            mesh, sh, step = world(mc, n)
            shapes = jax.eval_shape(lambda s: s, state)
            new_state, report = reshard_state(
                state, spec_of(sh, shapes)
            )
            fn = compiled(step, mesh, new_state)
            new_state, _ = fn(new_state, xj, xj)
            jax.block_until_ready(new_state.params)
            return new_state, report

        t0 = _time.perf_counter()
        state, rep_cold = move(state, mc_b, 8)  # cold: never compiled
        cold_pp_ms = (_time.perf_counter() - t0) * 1e3
        t0 = _time.perf_counter()
        state, rep_warm = move(state, mc_a, 4)  # warm: AOT cache hit
        warm_pp_ms = (_time.perf_counter() - t0) * 1e3
        results["resize_downtime_cold_pp_ms"] = round(cold_pp_ms, 2)
        results["resize_downtime_warm_pp_ms"] = round(warm_pp_ms, 2)
        results["resize_pp_axis_changes"] = (
            rep_cold.describe_axis_changes()
        )
        results["resize_pp_note"] = (
            "dp2xpp2 -> dp4xpp2 (cold) -> dp2xpp2 (warm AOT hit): "
            "stage-stacked state resharded on device (dp absorbs the "
            "delta, stages stay put), explicit per-stage sync "
            "re-planned per world"
        )
    except Exception as e:
        results["resize_pp_error"] = repr(e)


# compressed training must land within this of the fp32 baseline's
# final loss on the grad-sync scenario (24 adamw steps, tiny model):
# the documented convergence gate for int8 + error feedback. Measured
# headroom: the CPU smoke run lands ~0.005-0.02 apart; 0.05 fails
# loudly when error feedback breaks (EF-less int8 drifts ~0.1+ here)
GRAD_SYNC_LOSS_GATE = 0.05
# int8 wire bytes must be <= this fraction of the raw fp32 sync bytes
# (1B payload + per-bucket scale vs 4B/elem => ~0.25 + padding)
GRAD_SYNC_WIRE_GATE = 0.30


def run_grad_sync_bench(jax, results: dict, smoke: bool = False):
    """Overlap-scheduled gradient sync: bucketed shard_map collectives
    + int8 compression with error feedback (parallel/grad_sync.py).

    Scenario (2-device DP, tiny model, fixed data, identical init):
    train the same run three ways —

    - **fp32 baseline**: GSPMD's default monolithic sync;
    - **comm_overlap**: explicit bucketed reduce-scatter — must match
      the baseline numerically (same math, different schedule);
    - **comm_overlap + int8**: quantized wire payloads with error
      feedback — final loss must land within ``GRAD_SYNC_LOSS_GATE``
      of the baseline and wire bytes within ``GRAD_SYNC_WIRE_GATE``
      of raw, or ``--smoke`` exits nonzero (the compression path
      cannot silently rot).

    Keys: ``grad_sync_ms`` (standalone bucketed-sync wall time — its
    roofline; the in-step cost is lower by whatever the scheduler
    overlaps), ``comm_overlap_pct`` (measured-on-accelerator /
    analytic-on-CPU hidden fraction, labeled), and
    ``grad_bytes_wire_vs_raw`` ([wire, raw] per sync).
    """
    import optax

    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.train import (
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.parallel.grad_sync import (
        ensure_residual,
        estimate_overlap_pct,
        measure_sync_ms,
        resolve_plan,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    devs = list(jax.devices())[:2]
    if len(devs) < 2:
        results["grad_sync_error"] = "needs >= 2 devices"
        return
    cfg = tiny(num_layers=1) if smoke else tiny()
    cfg = replace(cfg, dtype="float32", param_dtype="float32")
    mesh = build_mesh(MeshConfig(dp=2), devices=devs)
    tx = optax.adamw(1e-2)
    # ONE plan source for the residual AND the reporting, resolved the
    # same way build_train_step resolves it (same gate, same bucket
    # target) — a hand-built twin plan could drift in padding/shape
    strategy = Strategy(
        mesh=MeshConfig(dp=2), dtype="float32",
        comm_overlap=True, grad_compress="int8", grad_bucket_mb=1,
    )
    plan = resolve_plan(cfg, strategy)
    steps = 24
    batch, seq = 8, 32
    rng = np.random.default_rng(0)
    data = [
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        for _ in range(4)
    ]

    def run(comm_overlap: bool, compress: str) -> float:
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_train_step(
            cfg, mesh, tx, donate=False,
            comm_overlap=comm_overlap, grad_compress=compress,
            grad_bucket_mb=strategy.grad_bucket_mb,
        )
        if compress == "int8":
            state = ensure_residual(state, plan, mesh)
        for i in range(steps):
            x = data[i % len(data)]
            b = shard_batch({"x": x, "y": x}, mesh)
            state, metrics = step(state, b["x"], b["y"])
        return float(metrics["loss"])

    loss_fp32 = run(False, "none")
    loss_overlap = run(True, "none")
    loss_int8 = run(True, "int8")

    results["grad_sync_ms"] = round(measure_sync_ms(plan, mesh), 3)
    # real overlap needs an accelerator profile to measure; until a
    # profile-reader lands this is the documented model constant on
    # every backend (grad_sync.OVERLAP_HIDDEN_FRACTION), labeled so
    results["comm_overlap_pct"] = estimate_overlap_pct(strategy)
    results["comm_overlap_pct_source"] = "analytic"
    results["grad_bytes_wire_vs_raw"] = [
        plan.wire_bytes, plan.raw_bytes
    ]
    results["grad_sync_wire_ratio"] = round(
        plan.wire_bytes / plan.raw_bytes, 4
    )
    results["grad_sync_buckets"] = plan.num_buckets
    results["grad_sync_loss_fp32"] = round(loss_fp32, 5)
    results["grad_sync_loss_overlap"] = round(loss_overlap, 5)
    results["grad_sync_loss_int8"] = round(loss_int8, 5)
    results["grad_sync_loss_gap"] = round(
        abs(loss_int8 - loss_fp32), 5
    )
    results["grad_sync_loss_gate"] = GRAD_SYNC_LOSS_GATE
    results["grad_sync_note"] = (
        "2-device DP, identical init/data: fp32 GSPMD baseline vs "
        "explicit bucketed sync vs int8+error-feedback; gates: "
        f"int8 final loss within {GRAD_SYNC_LOSS_GATE} of fp32, wire "
        f"bytes <= {GRAD_SYNC_WIRE_GATE:.0%} of raw"
    )


def run_topology_bench(jax, results: dict, smoke: bool = False):
    """Measured link-cost model + two-level multi-slice gradient sync
    (parallel/topology.py, grad_sync's hierarchical schedule).

    Three legs:

    - **probe smoke**: ``probe_link_model`` must produce a ``LinkModel``
      with sane ordering (ici >= dcn >= host link — a model violating
      it would invert every scheduling decision built on it; the
      virtual CPU backend gets the documented fallback constants,
      labeled), and a second probe must hit the persisted per-
      fingerprint cache — the warm-restart/resize invariant
      (docs/elastic-resize.md: re-probe only on fingerprint change);
    - **two-level vs flat A/B** on an emulated 2-slice mesh (dp over 2
      DCN slices, CPU virtual backend): the hierarchical schedule must
      move strictly fewer cross-slice bytes than the flat ring
      (``grad_sync_2level_wire_vs_flat`` < 1.0) while training
      bit-identically to GSPMD's monolithic all-reduce in fp32;
    - **model-driven pricing**: the dry-runner's exposed-comm seconds
      must move when the installed ``LinkModel``'s DCN rate moves —
      ``est_step_s`` is priced from the probe, not the legacy
      ``_SEC_PER_ICI_BYTE`` constant.

    Keys: ``link_ici_GBps`` / ``link_dcn_GBps`` / ``link_host_GBps`` /
    ``link_ordering_ok`` / ``link_model_source`` /
    ``topology_probe_cache_hit`` / ``grad_sync_2level_wire_vs_flat`` /
    ``grad_sync_2level_parity`` / ``grad_sync_ici_ms`` /
    ``grad_sync_dcn_ms`` / ``dry_run_priced_from_link_model``.
    """
    import optax

    from dlrover_tpu.accel.dry_runner import DryRunReport, _comm_estimate
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.train import (
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.parallel import topology
    from dlrover_tpu.parallel.grad_sync import (
        measure_sync_legs_ms,
        resolve_plan,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    devs = list(jax.devices())
    dp = 8 if len(devs) >= 8 else 4 if len(devs) >= 4 else 0
    if not dp:
        results["topology_error"] = "needs >= 4 devices"
        return
    devs = devs[:dp]
    cache = tempfile.mkdtemp(prefix="bench_topo_")
    topology.reset_link_model()
    try:
        # -- leg 1: probe + warm-cache hit ---------------------------
        m1 = topology.probe_link_model(
            devices=devs, force=True, cache_dir=cache
        )
        m2 = topology.probe_link_model(devices=devs, cache_dir=cache)
        results["link_ici_GBps"] = round(m1.ici_gbps, 3)
        results["link_dcn_GBps"] = round(m1.dcn_gbps, 3)
        results["link_host_GBps"] = round(
            min(m1.host_d2h_gbps, m1.host_h2d_gbps), 3
        )
        results["link_model_source"] = m1.source
        results["link_ordering_ok"] = bool(m1.ordering_ok)
        results["topology_probe_cache_hit"] = bool(m2 == m1)

        # -- leg 2: two-level vs flat on an emulated 2-slice mesh ----
        cfg = replace(
            tiny(num_layers=1), dtype="float32", param_dtype="float32"
        )
        mc = MeshConfig(dp=dp, dcn_axes=("dp",), slices=2)
        mesh = build_mesh(mc, devices=devs)
        strategy = Strategy(
            mesh=mc, dtype="float32", comm_overlap=True,
            grad_bucket_mb=1,
        )
        plan = resolve_plan(cfg, strategy)
        results["grad_sync_2level_dcn_bytes"] = [
            plan.dcn_bytes_twolevel(), plan.dcn_bytes_flat()
        ]
        results["grad_sync_2level_wire_vs_flat"] = round(
            plan.dcn_bytes_twolevel() / plan.dcn_bytes_flat(), 4
        )
        tx = optax.adamw(1e-2)
        rng = np.random.default_rng(0)
        x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
        b = shard_batch({"x": x, "y": x}, mesh)

        def run(comm_overlap: bool, slices: int) -> float:
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False,
                comm_overlap=comm_overlap, grad_bucket_mb=1,
                grad_slices=slices,
            )
            for _ in range(8):
                state, metrics = step(state, b["x"], b["y"])
            return float(metrics["loss"])

        loss_gspmd = run(False, 1)
        loss_2level = run(True, 2)
        results["grad_sync_loss_gspmd"] = round(loss_gspmd, 6)
        results["grad_sync_loss_2level"] = round(loss_2level, 6)
        # fp32 bit-parity: same math, different schedule — any drift
        # here is a reduction-order/correctness bug, not noise
        results["grad_sync_2level_parity"] = bool(
            loss_2level == loss_gspmd
        )
        ici_ms, dcn_ms = measure_sync_legs_ms(plan, mesh, iters=3)
        results["grad_sync_ici_ms"] = round(ici_ms, 3)
        results["grad_sync_dcn_ms"] = round(dcn_ms, 3)

        # -- leg 3: dry-runner prices from the installed model -------
        fp = topology.device_fingerprint(devs)

        def exposed(dcn_gbps: float) -> float:
            topology.set_link_model(
                topology.LinkModel(
                    ici_gbps=90.0, dcn_gbps=dcn_gbps,
                    source="measured", fingerprint=fp,
                ),
                devices=devs,
            )
            r = DryRunReport(strategy=strategy, ok=True)
            _comm_estimate(r, cfg, 8, 32, devs)
            return r.comm_exposed_s

        fast, slow = exposed(100.0), exposed(1.0)
        results["dry_run_priced_from_link_model"] = bool(
            slow > fast > 0
        )
        results["topology_note"] = (
            f"{dp}-dev 2-slice emulated mesh: two-level sync crosses "
            f"{results['grad_sync_2level_wire_vs_flat']:.0%} of the "
            "flat ring's DCN bytes at fp32 bit parity; probe cached "
            f"per fingerprint ({m1.fingerprint})"
        )
    finally:
        # the installed test models must not leak into later legs
        topology.reset_link_model()


# the sparse DCN shard (k int8 blocks + 4B indices at density 0.25)
# must undercut the dense int8 shard by at least half, or the top-k
# leg is not paying for its EF noise
SPARSE_SYNC_DCN_WIRE_GATE = 0.5


def run_sparse_sync_bench(jax, results: dict, smoke: bool = False):
    """Sparse DCN gradient sync (ISSUE 18): EF-composed block top-k on
    the two-level sync's cross-slice leg, plus the observed rail-rate
    loop that folds realized striped-transfer throughput back into the
    link-cost model.

    Legs (emulated 2-slice mesh on the CPU backend):

    - **wire math + convergence A/B**: the same run trained dense
      two-level fp32, int8, and int8+topk(0.25). Gates: sparse DCN
      bytes <= ``SPARSE_SYNC_DCN_WIRE_GATE`` x the int8 shard, final
      loss within ``GRAD_SYNC_LOSS_GATE`` of the fp32 baseline (EF
      drains the unshipped blocks — measured gap ~0.02 at 56 steps);
    - **density-1.0 bitwise**: ``int8_topk`` at density 1.0 must
      reproduce the dense int8 sync bit for bit (mask all-ones,
      ``xx * 1.0`` IEEE-exact) — the sparse branch cannot drift from
      the path it generalizes;
    - **observed rail rates**: one striped transfer over
      LinkModel-priced rails must fold realized GB/s into
      ``topology.observe_rail_rate``, persist per fingerprint
      (``topology_observed_rates_persisted``), and survive a full
      model reset — ``get_link_model()`` after the reset reprices the
      DCN leg from the disk snapshot (the cache round trip).
    """
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.train import (
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.parallel import topology
    from dlrover_tpu.parallel.grad_sync import (
        ensure_residual,
        plan_buckets,
        resolve_auto_compress,
        resolve_plan,
        sync_grads,
        zero_residual,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.transfer_sched import (
        StripedTransfer,
        TransferArbiter,
    )

    devs = list(jax.devices())
    if len(devs) < 4:
        results["sparse_sync_error"] = "needs >= 4 devices"
        return
    devs = devs[:4]
    cache = tempfile.mkdtemp(prefix="bench_sparse_sync_")
    env_prev = os.environ.get("DLROVER_TPU_TOPOLOGY_CACHE")
    os.environ["DLROVER_TPU_TOPOLOGY_CACHE"] = cache
    topology.reset_link_model()
    try:
        # -- leg 1: wire math + convergence A/B ----------------------
        cfg = replace(
            tiny(num_layers=1), dtype="float32", param_dtype="float32"
        )
        mc = MeshConfig(dp=4, dcn_axes=("dp",), slices=2)
        mesh = build_mesh(mc, devices=devs)

        def plan_for(compress):
            return resolve_plan(
                cfg,
                Strategy(
                    mesh=mc, dtype="float32", comm_overlap=True,
                    grad_compress=compress, grad_bucket_mb=1,
                    grad_topk_density=0.25,
                ),
            )

        p_fp32, p_int8, p_topk = (
            plan_for("none"), plan_for("int8"), plan_for("int8_topk")
        )
        results["grad_sync_dcn_bytes_fp32_int8_topk"] = [
            p_fp32.dcn_bytes_twolevel(),
            p_int8.dcn_bytes_twolevel(),
            p_topk.dcn_bytes_twolevel(),
        ]
        results["grad_sync_dcn_wire_vs_int8"] = round(
            p_topk.dcn_bytes_twolevel() / p_int8.dcn_bytes_twolevel(),
            4,
        )
        results["grad_sync_dcn_density"] = round(p_topk.dcn_density, 4)
        # the auto policy on this (fallback-priced) topology: the
        # 90:12.5 ICI:DCN ratio crosses AUTO_TOPK_RATIO -> sparse
        results["grad_compress_auto_mode"] = resolve_auto_compress(
            slices=2
        )

        tx = optax.adamw(1e-2)
        rng = np.random.default_rng(0)
        x = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
        b = shard_batch({"x": x, "y": x}, mesh)

        def run(compress: str) -> float:
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False, comm_overlap=True,
                grad_compress=compress, grad_bucket_mb=1,
                grad_slices=2, grad_topk_density=0.25,
            )
            state = ensure_residual(state, plan_for(compress), mesh)
            # 56 steps: past the EF catch-up knee (see
            # tests/test_sparse_sync.py's measured gap-vs-steps curve)
            for _ in range(56):
                state, metrics = step(state, b["x"], b["y"])
            return float(metrics["loss"])

        loss_fp32 = run("none")
        loss_topk = run("int8_topk")
        results["sparse_sync_loss_fp32"] = round(loss_fp32, 6)
        results["sparse_sync_loss_topk"] = round(loss_topk, 6)
        results["sparse_sync_loss_gap"] = round(
            abs(loss_topk - loss_fp32), 6
        )

        # -- leg 2: density-1.0 bitwise == int8 ----------------------
        from jax.sharding import NamedSharding, PartitionSpec as P

        g = np.asarray(
            rng.standard_normal((4, 4000)), dtype=np.float32
        )
        shapes = {"w": jax.ShapeDtypeStruct((4000,), jnp.float32)}
        kw = dict(dp=4, slices=2, bucket_bytes=1 << 20)
        bitwise = []
        for compress, density in (("int8", 1.0), ("int8_topk", 1.0)):
            plan = plan_buckets(
                shapes, compress=compress, topk_density=density, **kw
            )
            sh = NamedSharding(mesh, P(plan.stack_axes))
            stacked = {"w": jax.device_put(g, sh)}
            synced, res, _ = jax.jit(
                lambda t, r, p=plan: sync_grads(t, mesh, p, residual=r)
            )(stacked, zero_residual(plan, mesh))
            bitwise.append(
                (
                    np.asarray(synced["w"]).tobytes(),
                    np.asarray(res[0]).tobytes(),
                )
            )
        results["sparse_sync_density1_bitwise"] = bool(
            bitwise[0] == bitwise[1]
        )

        # -- leg 3: observed rail rates close the pricing loop -------
        base_dcn = topology.get_link_model(devices=devs).dcn_gbps
        arb = TransferArbiter()
        arb.register_rail("host_d2h", direction="d2h")
        arb.register_rail("dcn", direction="peer")
        src = bytearray(32 << 20)
        dst = bytearray(32 << 20)

        def mover(rail, off, ln):
            dst[off:off + ln] = src[off:off + ln]

        StripedTransfer(
            arb, direction="d2h", chunk_bytes=4 << 20,
            ignore_window=True,
        ).run(mover, payload=src)
        rates = topology.get_rail_rates()
        fp = topology.device_fingerprint()
        persisted = os.path.exists(topology.rail_rates_path(fp))
        results["topology_observed_rates_persisted"] = int(
            bool(rates and rates.gbps and persisted)
        )
        results["link_observed_gbps"] = {
            k: round(v, 4) for k, v in (rates.gbps if rates else {}).items()
        }
        # cache round trip: drop every in-process model/rate, then
        # get_link_model() must come back repriced from the disk
        # snapshot rather than the fallback constant
        topology.reset_link_model()
        m = topology.get_link_model()
        observed_dcn = (rates.gbps if rates else {}).get("peer")
        results["topology_observed_pricing"] = bool(
            observed_dcn is not None
            and abs(m.dcn_gbps - observed_dcn) < 1e-9
            and m.dcn_gbps != base_dcn
        )
        results["sparse_sync_note"] = (
            "4-dev 2-slice emulated mesh: top-k DCN shard at density "
            f"{results['grad_sync_dcn_density']} ships "
            f"{results['grad_sync_dcn_wire_vs_int8']:.0%} of the int8 "
            "shard's bytes; EF closes the loss gap to "
            f"{results['sparse_sync_loss_gap']} by step 56; one "
            "striped transfer reprices the DCN leg through the "
            "persisted observed-rate EWMA"
        )
    finally:
        topology.reset_link_model()
        if env_prev is None:
            os.environ.pop("DLROVER_TPU_TOPOLOGY_CACHE", None)
        else:
            os.environ["DLROVER_TPU_TOPOLOGY_CACHE"] = env_prev


# the dp x tp explicit sync runs the same psum in the same order as
# GSPMD's, but the partitioner makes different matmul splits inside vs
# outside the partial-manual region — parity is float-noise-tight
# (measured ~2e-7 after 6 steps) rather than bitwise; dp x fsdp IS
# bitwise (the ZeRO composition reproduces GSPMD's reduction grouping)
HYBRID_TP_PARITY_GATE = 1e-5


def run_hybrid_sync_bench(jax, results: dict, smoke: bool = False):
    """Hybrid-mesh overlap sync (ISSUE 8): the explicit bucketed
    gradient sync on model-sharded meshes.

    Three legs on emulated CPU meshes:

    - **dp2 x fsdp2 three-way** (GSPMD / explicit ZeRO / int8+EF):
      the explicit path must engage (``hybrid_sync_path_fsdp=
      explicit``, no GSPMD-fallback log), train **bitwise-identical**
      to GSPMD at fp32 (the ZeRO reduce-scatter-into-shards schedule
      reproduces GSPMD's own reduction grouping), move strictly fewer
      ring bytes than the monolithic all-reduce
      (``hybrid_sync_fsdp_wire_bytes < hybrid_sync_gspmd_wire_
      bytes`` — no fsdp all-gather leg, dp legs ride the 1/fsdp
      chunk), and the int8+error-feedback composition on the dp axis
      must land within ``GRAD_SYNC_LOSS_GATE`` of the fp32 baseline;
    - **dp2 x tp2 A/B** (GSPMD / explicit): the bucketed dp-axis sync
      runs under the GSPMD tp submesh (partial-manual shard_map);
      parity is gated at ``HYBRID_TP_PARITY_GATE`` (see the constant:
      the sync is order-identical, the matmul partitioning is not);
    - **warm dp x tp resize**: an ElasticTrainer on dp2 x tp2 resizes
      to dp4 x tp2 (cold) and back (warm, AOT cache hit) — the
      per-dimension reshard path at work on a model-sharded mesh,
      reported as ``resize_downtime_warm_tp_ms`` alongside the
      DP-only ``resize_downtime_warm_ms``.
    """
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.train import (
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.parallel import grad_sync
    from dlrover_tpu.parallel.grad_sync import (
        ensure_residual,
        plan_for_mesh,
        resolve_plan,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    devs = list(jax.devices())
    if len(devs) < 4:
        results["hybrid_sync_error"] = (
            f"hybrid sync bench needs >= 4 devices, have {len(devs)}"
        )
        return
    cfg = tiny(num_layers=1) if smoke else tiny()
    cfg = replace(cfg, dtype="float32", param_dtype="float32")
    tx = optax.adamw(1e-2)
    steps = 6 if smoke else 12
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)

    def run(mesh, comm_overlap: bool, compress: str) -> float:
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_train_step(
            cfg, mesh, tx, donate=False,
            comm_overlap=comm_overlap, grad_compress=compress,
            grad_bucket_mb=1,
        )
        if compress == "int8":
            plan = plan_for_mesh(
                cfg, mesh, grad_compress="int8", grad_bucket_mb=1
            )
            state = ensure_residual(state, plan, mesh)
        b = shard_batch({"x": x, "y": x}, mesh)
        for _ in range(steps):
            state, metrics = step(state, b["x"], b["y"])
        return float(metrics["loss"])

    def fallback_key(mc):
        sizes = mc.axis_sizes()
        return tuple(sorted((k, int(v)) for k, v in sizes.items()))

    # -- leg 1: dp2 x fsdp2 three-way ------------------------------------
    mc_fsdp = MeshConfig(dp=2, fsdp=2)
    mesh_fsdp = build_mesh(mc_fsdp, devices=devs[:4])
    plan = resolve_plan(
        cfg,
        Strategy(
            mesh=mc_fsdp, dtype="float32", comm_overlap=True,
            grad_bucket_mb=1,
        ),
    )
    results["hybrid_sync_path_fsdp"] = (
        "explicit" if plan is not None else "gspmd"
    )
    results["hybrid_sync_fsdp_wire_bytes"] = plan.explicit_wire_bytes()
    results["hybrid_sync_gspmd_wire_bytes"] = (
        plan.gspmd_allreduce_bytes()
    )
    results["hybrid_sync_fsdp_wire_vs_gspmd"] = round(
        plan.explicit_wire_bytes() / plan.gspmd_allreduce_bytes(), 4
    )
    loss_gspmd = run(mesh_fsdp, False, "none")
    loss_zero = run(mesh_fsdp, True, "none")
    loss_int8 = run(mesh_fsdp, True, "int8")
    results["hybrid_sync_loss_fsdp_gspmd"] = round(loss_gspmd, 6)
    results["hybrid_sync_loss_fsdp_explicit"] = round(loss_zero, 6)
    # fp32 bit parity: same math, same reduction grouping — any drift
    # is a correctness bug, not noise
    results["hybrid_sync_parity_fsdp"] = bool(loss_zero == loss_gspmd)
    results["hybrid_sync_int8_loss_gap"] = round(
        abs(loss_int8 - loss_gspmd), 5
    )

    # -- leg 2: dp2 x tp2 A/B --------------------------------------------
    mc_tp = MeshConfig(dp=2, tp=2)
    mesh_tp = build_mesh(mc_tp, devices=devs[:4])
    plan_tp = resolve_plan(
        cfg,
        Strategy(
            mesh=mc_tp, dtype="float32", comm_overlap=True,
            grad_bucket_mb=1,
        ),
    )
    results["hybrid_sync_path_tp"] = (
        "explicit" if plan_tp is not None else "gspmd"
    )
    loss_tp_gspmd = run(mesh_tp, False, "none")
    loss_tp_expl = run(mesh_tp, True, "none")
    results["hybrid_sync_loss_tp_gspmd"] = round(loss_tp_gspmd, 6)
    results["hybrid_sync_loss_tp_explicit"] = round(loss_tp_expl, 6)
    results["hybrid_sync_tp_loss_gap"] = abs(
        loss_tp_expl - loss_tp_gspmd
    )
    results["hybrid_sync_parity_tp"] = bool(
        abs(loss_tp_expl - loss_tp_gspmd) <= HYBRID_TP_PARITY_GATE
    )
    # neither mesh may have taken the silent fallback (the once-per-
    # mesh log also records which meshes fell back)
    results["hybrid_sync_no_fallback_log"] = bool(
        fallback_key(mc_fsdp) not in grad_sync._GSPMD_FALLBACK_LOGGED
        and fallback_key(mc_tp) not in grad_sync._GSPMD_FALLBACK_LOGGED
    )

    # -- leg 3: warm dp x tp resize via the AOT cache --------------------
    if len(devs) < 8:
        results["hybrid_resize_note"] = (
            "skipped: resize leg needs 8 devices"
        )
        return
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    class _Tokens:
        def __init__(self, n=128, seq=32, vocab=256):
            rng = np.random.default_rng(0)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    trainer = ElasticTrainer(
        model_cfg=tiny(num_layers=1) if smoke else tiny(),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            report_metrics=False,
            log_interval=1000,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
            comm_overlap=True,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=2, tp=2), dtype="float32"),
        devices=devs[:4],
    )
    try:
        results["hybrid_sync_path_trainer"] = (
            trainer.pipeline_stats.grad_sync_path
        )
        trainer.train(num_steps=2)
        cold = trainer.resize(8)  # dp4 x tp2: never compiled
        trainer.train(num_steps=4)
        warm = trainer.resize(4)  # back to dp2 x tp2: AOT cache hit
        trainer.train(num_steps=6)
        results["resize_downtime_cold_tp_ms"] = round(
            cold["downtime_ms"], 2
        )
        results["resize_downtime_warm_tp_ms"] = round(
            warm["downtime_ms"], 2
        )
        results["hybrid_resize_cache_hit"] = bool(
            warm["compile_cache_hit"]
        )
        results["hybrid_resize_note"] = (
            "dp2xtp2 -> dp4xtp2 (cold) -> dp2xtp2 (warm AOT hit): the "
            "per-dimension reshard keeps tp shards on device while dp "
            "absorbs the delta; explicit sync re-planned per world"
        )
    finally:
        trainer.close()


# tracer overhead gate (docs/observability.md): with tracing enabled the
# measured step time may exceed the disabled baseline by at most this —
# the span tracer's contract is "cheap enough to leave on in production"
TRACER_OVERHEAD_GATE_PCT = 2.0
# absolute noise floor: back-to-back CPU step timings jitter by more
# than a tracer costs; a delta under this per step is below what the
# A/B can resolve and passes regardless of the ratio
TRACER_OVERHEAD_FLOOR_MS = 0.25
# the dumped trace's step spans must be explained by their phase
# children to at least this fraction (the "where did the wall time go"
# contract)
TRACE_COVERAGE_GATE_PCT = 95.0


def run_trace_bench(jax, results: dict, smoke: bool = False):
    """Span-tracer overhead gate + Chrome-trace artifact.

    Scenario: one ElasticTrainer (tiny model, single device), stepped
    in short alternating segments with tracing enabled vs disabled
    (same compiled step, same data). The A/B is drift-hardened — a
    settling run burns off the decaying background load earlier bench
    legs leave behind (thread teardown, GC, page cache), each pair
    flips which arm runs first, and the overhead is the MEDIAN of the
    per-pair deltas, so both monotone drift and one-off stalls (epoch
    rollover, GC pause) cancel instead of landing on one arm. Then one
    traced segment is dumped as a Chrome trace-event JSON
    (``trace_smoke.json`` under ``--smoke``) and validated: loadable,
    well-formed, and the ``step`` spans' phase children (data_wait /
    compute / host_sync / ckpt / report) must cover ≥
    ``TRACE_COVERAGE_GATE_PCT`` of step wall time.

    Keys: ``trace_step_ms_on`` / ``trace_step_ms_off`` /
    ``trace_overhead_pct`` (gated ≤ ``TRACER_OVERHEAD_GATE_PCT`` with
    the ``TRACER_OVERHEAD_FLOOR_MS`` absolute noise floor),
    ``trace_step_coverage_pct``, ``trace_valid``, ``trace_artifact``.
    """
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.obs import trace as obs_trace
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    class _Tokens:
        # big enough that the measured window never crosses an epoch
        # rollover (prefetcher rebuild would land in one arm)
        def __init__(self, n=2048, seq=32, vocab=256):
            rng = np.random.default_rng(7)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    tracer = obs_trace.get_tracer()
    was_enabled = tracer.enabled
    trainer = ElasticTrainer(
        model_cfg=tiny(num_layers=1) if smoke else tiny(),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            report_metrics=False,
            log_interval=4,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=1), dtype="float32"),
        devices=list(jax.devices())[:1],
    )
    try:
        def seg(n: int) -> float:
            """Per-step seconds over the next n optimizer steps."""
            target = trainer.global_step + n
            t0 = time.perf_counter()
            trainer.train(num_steps=target)
            return (time.perf_counter() - t0) / n

        trainer.train(num_steps=3)  # compile + warmup outside timing
        settle, steps, pairs = (16, 4, 8) if smoke else (32, 8, 10)
        # settle: earlier legs' teardown decays over seconds; burn it
        # off untimed so it doesn't masquerade as tracer cost
        trainer.train(num_steps=trainer.global_step + settle)
        deltas, offs = [], []
        for i in range(pairs):
            first_on = bool(i % 2)  # flip order every pair
            tracer.enabled = first_on
            a = seg(steps)
            tracer.enabled = not first_on
            b = seg(steps)
            t_on_i, t_off_i = (a, b) if first_on else (b, a)
            deltas.append(t_on_i - t_off_i)
            offs.append(t_off_i)
        t_off = float(np.median(offs))
        delta = float(np.median(deltas))
        t_on = t_off + delta
        overhead_pct = max(0.0, delta / t_off * 100.0)

        # deterministic per-span cost bound: on shared/noisy hosts the
        # wall A/B's per-segment jitter (± ms) swamps a µs-scale
        # effect, so the gate falls back to (measured span cost) ×
        # (spans per step) — a tracer that actually got expensive
        # (say 50µs/span) fails this bound loudly, while scheduler
        # noise cannot fake a failure
        tracer.enabled = True
        probe_n = 20_000
        pt0 = time.perf_counter()
        for _ in range(probe_n):
            with obs_trace.span("overhead_probe"):
                pass
        span_cost_s = (time.perf_counter() - pt0) / probe_n
        overhead_ok = (
            overhead_pct <= TRACER_OVERHEAD_GATE_PCT
            or delta * 1e3 <= TRACER_OVERHEAD_FLOOR_MS
        )

        # the artifact: one freshly-traced segment, dumped + validated
        tracer.reset()  # drop the probe spans before the artifact
        trainer.train(num_steps=trainer.global_step + 2 * steps)
        path = os.getenv(
            "DLROVER_TPU_TRACE_OUT",
            os.path.join(
                artifacts_dir(),
                "trace_smoke.json" if smoke else "trace_bench.json",
            ),
        )
        tracer.dump(path)
        with open(path) as f:
            loaded = json.load(f)
        valid, reason = obs_trace.validate_chrome_trace(loaded)
        coverage = obs_trace.step_coverage(loaded)
        xs = [
            e for e in loaded.get("traceEvents", [])
            if e.get("ph") == "X"
        ]
        n_steps = sum(1 for e in xs if e["name"] == "step") or 1
        spans_per_step = len(xs) / n_steps
        bound_pct = span_cost_s * spans_per_step / t_off * 100.0
        overhead_ok = (
            overhead_ok or bound_pct <= TRACER_OVERHEAD_GATE_PCT
        )

        results["trace_step_ms_on"] = round(t_on * 1e3, 3)
        results["trace_step_ms_off"] = round(t_off * 1e3, 3)
        results["trace_overhead_pct"] = round(overhead_pct, 3)
        results["trace_overhead_gate_pct"] = TRACER_OVERHEAD_GATE_PCT
        results["trace_span_cost_us"] = round(span_cost_s * 1e6, 3)
        results["trace_spans_per_step"] = round(spans_per_step, 2)
        results["trace_overhead_bound_pct"] = round(bound_pct, 4)
        results["trace_overhead_ok"] = bool(overhead_ok)
        results["trace_valid"] = bool(valid)
        results["trace_valid_reason"] = reason
        results["trace_step_coverage_pct"] = (
            round(coverage * 100.0, 2) if coverage is not None else None
        )
        results["trace_artifact"] = path
        results["trace_events"] = len(loaded.get("traceEvents", []))
        results["trace_note"] = (
            "order-balanced on/off segment pairs after a settling run, "
            "median of per-pair deltas; overhead gate: wall A/B <= "
            f"{TRACER_OVERHEAD_GATE_PCT}% or <= "
            f"{TRACER_OVERHEAD_FLOOR_MS} ms/step absolute, with a "
            "deterministic (span cost x spans/step) bound as the "
            "noisy-host fallback; step-span child coverage >= "
            f"{TRACE_COVERAGE_GATE_PCT}%"
        )
    finally:
        tracer.enabled = was_enabled
        trainer.close()


# step-budget audit (ISSUE 19): an injected slowdown must be attributed
# to the right priced component within this many audited steps
AUDIT_ATTRIBUTION_STEP_GATE = 20


def run_audit_bench(jax, results: dict, smoke: bool = False):
    """Step-budget reconciliation leg (ISSUE 19): priced-vs-observed
    attribution, drift-vs-regression classification, auditor overhead.

    Scenario A (regression attribution): a real trainer on the CPU
    backend runs past the auditor's warmup baseline, then every
    prefetch pull is delayed through the existing chaos site
    (``prefetch.pull:delay:1.0``) — pure data starvation, compute
    untouched. Gates: the regression alarm names ``data_wait`` (not a
    neighbor component) within ``AUDIT_ATTRIBUTION_STEP_GATE`` audited
    steps of the injection, and the alarm leaves flight-recorder
    evidence (an ``audit_regression`` event, plus a bundle when the
    dump rate limiter allows one).

    Scenario B (price drift): a synthetic auditor whose compute budget
    is mispriced 1.5x below the observed span stream — inside the
    drift gate. The per-component EWMA must absorb it (corrected
    budget within 10% of observed) and the regression detector must
    stay silent: drift reprices, it never alarms.

    Overhead: the auditor's per-step collect+audit cost, measured
    deterministically over a synthetic record stream with the live
    run's spans-per-step shape, must stay under the existing
    ``TRACER_OVERHEAD_GATE_PCT`` of the measured live step time.

    Keys: ``audit_alarm_component`` / ``audit_alarm_steps`` /
    ``audit_neighbor_quiet`` / ``audit_baseline_quiet`` /
    ``audit_flight_evidence`` / ``audit_overhead_pct`` /
    ``audit_overhead_ok`` / ``audit_drift_factor`` /
    ``audit_drift_no_alarm`` / ``audit_drift_repriced_ok``.
    """
    import shutil

    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.common import faults
    from dlrover_tpu.models import tiny
    from dlrover_tpu.obs import flight_recorder as obs_flight
    from dlrover_tpu.obs import trace as obs_trace
    from dlrover_tpu.obs.audit import (
        WARMUP_STEPS,
        StepAuditor,
        StepBudget,
    )
    from dlrover_tpu.obs.metrics import MetricsRegistry
    from dlrover_tpu.obs.trace import SpanTracer
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    class _Tokens:
        def __init__(self, n=2048, seq=32, vocab=256):
            rng = np.random.default_rng(11)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    tracer = obs_trace.get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True
    flight_tmp = tempfile.mkdtemp(prefix="dlrover_audit_")
    prev_dir = os.environ.get(obs_flight.ENV_FLIGHT_DIR)
    os.environ[obs_flight.ENV_FLIGHT_DIR] = flight_tmp
    faults.reset()
    trainer = ElasticTrainer(
        model_cfg=tiny(num_layers=1),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            report_metrics=False,
            log_interval=4,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=1), dtype="float32"),
        devices=list(jax.devices())[:1],
    )
    aud = trainer._auditor
    try:
        # the default tracer is shared across bench legs and thread
        # ids get reused: discard anything buffered before this
        # trainer existed or a dead leg's steps would audit against
        # this budget
        aud.skip_to_now()
        # baseline: compile, then the warmup window + a healthy tail
        # (the observed-seeded budgets land at warmup end)
        trainer.train(num_steps=3)
        trainer.train(
            num_steps=trainer.global_step + WARMUP_STEPS + 4
        )
        aud.collect()
        results["audit_baseline_quiet"] = aud.alarm_components() == []

        # live step time (the overhead denominator)
        n_t = 8
        t0 = time.perf_counter()
        trainer.train(num_steps=trainer.global_step + n_t)
        step_s = (time.perf_counter() - t0) / n_t
        aud.collect()

        # spans-per-step shape of the live stream, for the synthetic
        # overhead probe below
        xs = [
            e
            for e in tracer.chrome_trace().get("traceEvents", [])
            if e.get("ph") == "X"
        ]
        n_live_steps = sum(1 for e in xs if e["name"] == "step") or 1
        spans_per_step = max(2, int(round(len(xs) / n_live_steps)))

        # scenario A: starve the input pipeline through the existing
        # chaos delay site; nothing else in the step changed
        faults.configure("prefetch.pull:delay:1.0")
        alarm_component = None
        steps_to_alarm = None
        injected_at = aud.steps_audited
        try:
            while (
                aud.steps_audited - injected_at
                < AUDIT_ATTRIBUTION_STEP_GATE
            ):
                trainer.train(num_steps=trainer.global_step + 2)
                aud.collect()
                if aud.alarm_components():
                    alarm_component = aud.alarm_components()[0]
                    steps_to_alarm = aud.steps_audited - injected_at
                    break
        finally:
            faults.configure("")
        alarmed = set(aud.alarm_components())
        results["audit_alarm_component"] = alarm_component
        results["audit_alarm_steps"] = steps_to_alarm
        results["audit_alarm_step_gate"] = AUDIT_ATTRIBUTION_STEP_GATE
        results["audit_neighbor_quiet"] = (
            alarm_component == "data_wait"
            and alarmed == {"data_wait"}
        )
        # the alarm's forensics: the event is always recorded; the
        # bundle additionally lands unless the 5s dump rate limiter
        # folded it into an earlier bundle's story
        noted = any(
            e.get("kind") == "audit_regression"
            for e in trainer._flight.events()
        )
        bundles = (
            [
                os.path.join(flight_tmp, d)
                for d in sorted(os.listdir(flight_tmp))
                if "audit_regression" in d
            ]
            if os.path.isdir(flight_tmp)
            else []
        )
        results["audit_flight_evidence"] = bool(noted)
        results["audit_flight_bundle_ok"] = bool(bundles)
        if bundles:
            keep = os.path.join(
                artifacts_dir(), os.path.basename(bundles[-1])
            )
            shutil.rmtree(keep, ignore_errors=True)
            shutil.copytree(bundles[-1], keep)
            results["audit_flight_bundle"] = keep

        # overhead: deterministic per-step audit cost over a synthetic
        # record stream shaped like the live one (same spans/step),
        # collected at the trainer's log cadence (export every 4
        # steps — one giant batched collect would scan a much larger
        # held buffer per step than production ever does), against the
        # measured live step time. Best of 3 reps sheds scheduler
        # noise a single timing can't.
        MS_NS = 1_000_000
        probe_steps = 64 if smoke else 256
        names = ["data_wait", "compute", "host_sync"]
        audit_cost_s = float("inf")
        for _rep in range(3):
            ptr = SpanTracer(enabled=True)
            paud = StepAuditor(
                tracer=ptr, budget=aud.budget(), tid_fn=lambda: 1
            )
            preg = MetricsRegistry()
            rep_cost = 0.0
            for i in range(probe_steps):
                base = i * 100 * MS_NS
                for j in range(spans_per_step - 1):
                    ptr._buf.append((
                        names[j % len(names)], 1,
                        base + j * MS_NS, MS_NS, 1, None,
                        next(ptr._seq),
                    ))
                    ptr._appended += 1
                ptr._buf.append((
                    "step", 1, base, 99 * MS_NS, 0, None,
                    next(ptr._seq),
                ))
                ptr._appended += 1
                if (i + 1) % 4 == 0:
                    a0 = time.perf_counter()
                    paud.export(preg)
                    rep_cost += time.perf_counter() - a0
            audit_cost_s = min(audit_cost_s, rep_cost)
        per_step_cost_s = audit_cost_s / probe_steps
        overhead_pct = per_step_cost_s / step_s * 100.0
        results["audit_step_ms"] = round(step_s * 1e3, 3)
        results["audit_cost_us_per_step"] = round(
            per_step_cost_s * 1e6, 3
        )
        results["audit_overhead_pct"] = round(overhead_pct, 4)
        # same contract as the tracer gate: ratio bound, with the
        # absolute floor for hosts whose smoke steps are so short that
        # a fixed few-hundred-microsecond cost dominates the ratio
        results["audit_overhead_ok"] = bool(
            overhead_pct <= TRACER_OVERHEAD_GATE_PCT
            or per_step_cost_s * 1e3 <= TRACER_OVERHEAD_FLOOR_MS
        )

        # scenario B: pure price drift — budget 1.5x under the stream,
        # inside the drift gate; the EWMA must absorb it silently
        dtr = SpanTracer(enabled=True)
        dbudget = StepBudget()
        dbudget.set_component("compute", 0.050, "priced")
        dbudget.set_component("data_wait", 0.005, "priced")
        drift_alarms = []
        daud = StepAuditor(
            tracer=dtr,
            budget=dbudget,
            on_alarm=lambda c, r, d: drift_alarms.append(c),
        )
        t = 0
        for _ in range(WARMUP_STEPS + 20):
            dtr._buf.append((
                "data_wait", 1, t, 5 * MS_NS, 1, None,
                next(dtr._seq),
            ))
            dtr._buf.append((
                "compute", 1, t + 5 * MS_NS, 75 * MS_NS, 1, None,
                next(dtr._seq),
            ))
            dtr._buf.append((
                "step", 1, t, 80 * MS_NS, 0, None, next(dtr._seq),
            ))
            dtr._appended += 3
            t += 80 * MS_NS
        daud.collect()
        factor = daud.drift_factors()["compute"]
        corrected = dbudget.component("compute") * factor
        results["audit_drift_factor"] = round(factor, 4)
        results["audit_drift_no_alarm"] = (
            drift_alarms == [] and daud.alarm_components() == []
        )
        results["audit_drift_repriced_ok"] = bool(
            abs(corrected - 0.075) / 0.075 <= 0.10
        )
        results["audit_note"] = (
            "prefetch.pull:delay:1.0 starves data_wait only; alarm "
            f"must name it within {AUDIT_ATTRIBUTION_STEP_GATE} "
            "audited steps while compute stays quiet. Overhead: "
            "deterministic collect cost per synthetic step (live "
            "spans/step shape) vs measured live step time, gate "
            f"{TRACER_OVERHEAD_GATE_PCT}% or "
            f"{TRACER_OVERHEAD_FLOOR_MS} ms/step absolute. Drift "
            "leg: 1.5x "
            "mispricing folds into the per-component EWMA (corrected "
            "budget within 10%) with zero regression alarms"
        )
    finally:
        faults.reset()
        if prev_dir is None:
            os.environ.pop(obs_flight.ENV_FLIGHT_DIR, None)
        else:
            os.environ[obs_flight.ENV_FLIGHT_DIR] = prev_dir
        tracer.enabled = was_enabled
        trainer.close()
        shutil.rmtree(flight_tmp, ignore_errors=True)


def run_recovery_bench(jax, results: dict, smoke: bool = False):
    """Checkpoint-integrity recovery leg: inject a torn shard write and
    a persistent-ENOSPC persist through the deterministic fault points
    (``common/faults.py``) and measure/assert the recovery contract:

    - a torn newest step is DETECTED at load, quarantined, and restore
      falls back to the previous verified step (``ckpt_recover_ms``
      times that detect+rollback+restore);
    - persistent ENOSPC drops the saver into shm-only degraded mode
      (visible in the metrics registry), and the first healthy persist
      exits it;
    - ``faults_triggered`` counts every injected fault that fired.

    ``--smoke`` exits nonzero on any undetected corruption or failed
    rollback — the durability path regressing must fail CI loudly.
    """
    import shutil

    import jax.numpy as jnp

    from dlrover_tpu.common import faults
    from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer, StorageType
    from dlrover_tpu.ckpt.saver import (
        AsyncCheckpointSaver,
        QUARANTINE_SUFFIX,
    )
    from dlrover_tpu.obs.metrics import default_registry

    faults.reset()
    AsyncCheckpointSaver.reset()
    tmp = tempfile.mkdtemp(prefix="dlrover_recovery_")
    try:
        # -- leg 1: torn shard write -> detect + rollback (sync path) --
        ckptr = FlashCheckpointer(os.path.join(tmp, "ckpt"))
        w_good = np.arange(4096.0, dtype=np.float32)
        assert ckptr.save_checkpoint(
            1, {"w": jnp.asarray(w_good), "step": 1}, StorageType.DISK
        )
        faults.configure("ckpt.shard_write:torn_write:1.0:1")
        ckptr.save_checkpoint(
            2,
            {"w": jnp.asarray(w_good * 2), "step": 2},
            StorageType.DISK,
        )
        faults.configure("")  # disarm, keep the trigger tally
        target = {"w": jnp.zeros(4096, jnp.float32), "step": 0}
        t0 = time.perf_counter()
        step, state = ckptr.load_checkpoint(target)
        recover_ms = (time.perf_counter() - t0) * 1e3
        torn_detected = any(
            QUARANTINE_SUFFIX in n for n in os.listdir(ckptr.checkpoint_dir)
        )
        rollback_ok = (
            step == 1
            and state is not None
            and np.array_equal(np.asarray(state["w"]), w_good)
        )
        results["ckpt_recover_ms"] = round(recover_ms, 2)
        results["recovery_torn_detected"] = torn_detected
        results["recovery_rollback_ok"] = bool(rollback_ok)

        # -- leg 2: persistent ENOSPC -> degraded mode + recovery ------
        from dlrover_tpu.ckpt.engine import CheckpointEngine

        saver = AsyncCheckpointSaver.start_async_saving_ckpt(
            local_shard_num=1
        )
        saver.persist_retries = 2
        saver.persist_backoff_base = 0.01
        saver.persist_backoff_cap = 0.02
        try:
            engine = CheckpointEngine()
            ckpt_dir2 = os.path.join(tmp, "ckpt2")
            faults.configure("ckpt.persist:enospc:1.0")
            engine.save_to_memory(
                1, {"w": jnp.arange(64.0)}, ckpt_dir2
            )
            deadline = time.time() + 30
            while time.time() < deadline and not saver.degraded:
                time.sleep(0.05)
            degraded = saver.degraded
            gauge_visible = (
                default_registry()
                .gauge("dlrover_ckpt_degraded_mode")
                .value
                == 1.0
            )
            faults.configure("")  # heal the disk, keep the tallies
            deadline = time.time() + 30
            saved = False
            while time.time() < deadline and not saved:
                saved = engine.save_to_memory(
                    2, {"w": jnp.arange(64.0) + 1}, ckpt_dir2
                )
                time.sleep(0.1)
            deadline = time.time() + 30
            while time.time() < deadline and saver.degraded:
                time.sleep(0.05)
            results["recovery_enospc_degraded"] = bool(
                degraded and gauge_visible
            )
            results["recovery_enospc_recovered"] = bool(
                saved and not saver.degraded
            )
        finally:
            AsyncCheckpointSaver.reset()
        results["faults_triggered"] = faults.triggered_total()
    finally:
        faults.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def run_forensics_bench(jax, results: dict, smoke: bool = False):
    """Goodput-ledger closure + crash-flight-recorder leg.

    Two contracts from obs/goodput.py and obs/flight_recorder.py:

    - **closure**: over a real traced training run, the ledger's
      categories must sum back to wall time within
      ``goodput_closure_gate_pct`` (= ``obs.goodput.CLOSURE_GATE_PCT``,
      1%) — interval arithmetic double- or under-claiming time would
      silently corrupt the number the Brain plans against;
    - **black box**: a trainer killed by an injected fault
      (``prefetch.pull:io_error`` through the PR-5 ``FaultPoint``
      registry) must leave a flight-recorder bundle whose embedded
      ``trace.json`` validates as Chrome trace JSON — the forensics
      path only matters if it works when the process actually dies.

    Keys: ``goodput_ledger_pct`` / ``goodput_closure_error_pct`` (gated)
    / ``goodput_productive_s`` / ``goodput_ledger_wall_s`` /
    ``flight_crash_injected`` / ``flight_bundle_ok`` /
    ``flight_trace_valid`` / ``flight_bundle``. ``--smoke`` exits
    nonzero when the closure gate misses or the crash leaves no valid
    bundle.
    """
    import shutil

    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.common import faults
    from dlrover_tpu.models import tiny
    from dlrover_tpu.obs import flight_recorder as obs_flight
    from dlrover_tpu.obs.goodput import CLOSURE_GATE_PCT
    from dlrover_tpu.obs.trace import get_tracer, validate_chrome_trace
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    class _Tokens:
        def __init__(self, n=2048, seq=32, vocab=256):
            rng = np.random.default_rng(11)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    def _make_trainer():
        return ElasticTrainer(
            model_cfg=tiny(num_layers=1) if smoke else tiny(),
            tx=optax.adamw(1e-2),
            dataset=_Tokens(),
            trainer_cfg=TrainerConfig(
                batch_size=8,
                seq_len=32,
                report_metrics=False,
                log_interval=4,
                prefetch=2,
                donation_aware=False,
                speculative_compile=False,
            ),
            strategy=Strategy(mesh=MeshConfig(dp=1), dtype="float32"),
            devices=list(jax.devices())[:1],
        )

    tracer = get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True

    # -- leg 1: goodput closure over a real traced run -----------------
    trainer = _make_trainer()
    try:
        trainer.train(num_steps=24 if smoke else 96)
        report = trainer._goodput.snapshot()
    finally:
        trainer.close()
    results["goodput_ledger_pct"] = round(report.goodput_pct, 2)
    results["goodput_closure_error_pct"] = round(
        report.closure_error_pct, 4
    )
    results["goodput_closure_gate_pct"] = CLOSURE_GATE_PCT
    results["goodput_ledger_wall_s"] = round(report.wall_s, 3)
    results["goodput_productive_s"] = round(
        report.seconds.get("productive_compute", 0.0), 3
    )
    results["goodput_data_stall_s"] = round(
        report.seconds.get("data_stall", 0.0), 3
    )
    results["goodput_other_s"] = round(
        report.seconds.get("other", 0.0), 3
    )

    # -- leg 2: injected crash -> flight-recorder bundle ---------------
    flight_tmp = tempfile.mkdtemp(prefix="dlrover_flight_")
    prev_dir = os.environ.get(obs_flight.ENV_FLIGHT_DIR)
    os.environ[obs_flight.ENV_FLIGHT_DIR] = flight_tmp
    faults.reset()
    crashed = False
    try:
        t2 = _make_trainer()
        try:
            # every producer pull now raises OSError; it is delivered
            # to the train thread in order and escapes _train_loop,
            # which is exactly the crash the recorder must survive
            faults.configure("prefetch.pull:io_error:1.0")
            t2.train(num_steps=t2.global_step + 8)
        except OSError:
            crashed = True
        finally:
            faults.configure("")
            t2.close()
        bundles = sorted(
            os.path.join(flight_tmp, d)
            for d in os.listdir(flight_tmp)
            if d.split("_")[1:2] == ["crash"]
        ) if os.path.isdir(flight_tmp) else []
        valid, reason = False, "no bundle"
        if bundles:
            with open(os.path.join(bundles[-1], "trace.json")) as f:
                valid, reason = validate_chrome_trace(json.load(f))
        results["flight_crash_injected"] = bool(crashed)
        results["flight_bundle_ok"] = bool(bundles)
        results["flight_trace_valid"] = bool(valid)
        results["flight_trace_valid_reason"] = reason
        results["flight_bundle"] = bundles[-1] if bundles else None
        results["flight_bundle_files"] = (
            sorted(os.listdir(bundles[-1])) if bundles else []
        )
        if bundles:
            # keep the artifact where the other bench artifacts live
            keep = os.path.join(
                artifacts_dir(), os.path.basename(bundles[-1])
            )
            shutil.rmtree(keep, ignore_errors=True)
            shutil.copytree(bundles[-1], keep)
            results["flight_bundle"] = keep
    finally:
        faults.reset()
        if prev_dir is None:
            os.environ.pop(obs_flight.ENV_FLIGHT_DIR, None)
        else:
            os.environ[obs_flight.ENV_FLIGHT_DIR] = prev_dir
        tracer.enabled = was_enabled
        shutil.rmtree(flight_tmp, ignore_errors=True)


def run_brain_bench(jax, results: dict, smoke: bool = False):
    """Brain cluster-scheduler closed-loop leg (ISSUE 10): 3 simulated
    jobs with unequal scaling curves on the local backend, one Brain
    with the ClusterScheduler over real gRPC, each job's PlanExecutor
    driving a real ``JobAutoScaler.scale_to`` — the full
    telemetry→decision→execution→feedback loop. Gates:

    - **(a) convergence**: the closed loop's aggregate goodput-weighted
      throughput must beat the best static equal split of the same chip
      budget (``brain_agg_goodput_closed`` vs
      ``brain_agg_goodput_equal_split``) — a scheduler that cannot beat
      "give everyone the same" is not earning its resize downtime;
    - **(b) latency**: ``brain_decision_to_resized_ms`` (median over
      executed slices, measured plan-emit wall time → scale_to done,
      over real gRPC) must be reported;
    - **(c) accounting**: every emitted plan slice ends acked-or-expired
      (``brain_plans_unresolved`` == 0, ``brain_plans_acked`` > 0) —
      silent drops are invisible exactly when the loop is broken.

    The simulated jobs report ``goodput_pct`` on their samples exactly
    the way real masters do (JobMetricCollector → persist_metrics), so
    the scheduler exercises the PR-7 goodput rows, not a parallel
    bookkeeping path. One job deliberately never polls its executor for
    the first rounds so plan expiry is exercised, then resumes.
    """
    import statistics

    from dlrover_tpu.brain.plan_exec import PlanExecutor
    from dlrover_tpu.brain.service import BrainClient, start_brain_service
    from dlrover_tpu.common import comm
    from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.job_manager import JobManager
    from dlrover_tpu.master.scaler import CallbackScaler

    total_chips = 12
    start_n = 4  # the best static equal split of 12 over 3 jobs
    # true (hidden) scaling curves: near-linear / knee / flat — the
    # heterogeneity the equal split cannot serve
    curves = {"bench-lin": 0.95, "bench-knee": 0.55, "bench-flat": 0.20}

    def true_speed(job: str, n: int) -> float:
        return 10.0 * max(0, n) ** curves[job]

    server, servicer, addr = start_brain_service(
        scheduler=True, total_chips=total_chips
    )
    sched = servicer.scheduler
    sched.stop()  # drive passes manually: deterministic rounds
    sched.min_dwell_s = 0.0  # sim rounds are seconds apart, not minutes
    sched.hysteresis_frac = 0.01
    jobs = {}
    try:
        for job in curves:
            jm = JobManager()
            jm.create_initial_nodes(start_n)
            auto = JobAutoScaler(
                jm,
                scaler=CallbackScaler(lambda plan: None),
                target_nodes=start_n,
            )
            cli = BrainClient(addr, job)
            jobs[job] = (auto, cli, PlanExecutor(cli, auto))

        rounds, skip_polls = (8, 2) if smoke else (12, 3)
        for rnd in range(rounds):
            for job, (auto, cli, _ex) in jobs.items():
                cli.persist_metrics(
                    comm.JobMetricsSample(
                        timestamp=time.time(),
                        alive_nodes=auto.target,
                        steps_per_sec=true_speed(job, auto.target),
                        goodput_pct=99.0,
                    )
                )
            sched.run_pass()
            for job, (_auto, _cli, ex) in jobs.items():
                # bench-flat goes dark for the first rounds: its slices
                # must EXPIRE (visibly), not silently vanish
                if job == "bench-flat" and rnd < skip_polls:
                    continue
                ex.poll_once()
        # a master that dies before ever polling leaves a pending slice
        # behind: emit one for a job with no executor, age every still-
        # pending slice past the TTL, and expire — the accounting gate:
        # the table must converge to acked-or-expired, never silently
        # dropped rows
        servicer.record_cluster_plan(
            servicer.next_plan_version(),
            [
                {
                    "job": "bench-zombie",
                    "worker_count": 2,
                    "prev_count": 4,
                    "reason": "master died before ack (expiry leg)",
                }
            ],
            time.time(),
        )
        with servicer._lock:
            servicer._conn.execute(
                "UPDATE cluster_plans SET ts = ts - ? "
                "WHERE status='pending'",
                (sched.plan_ttl_s + 1,),
            )
            servicer._conn.commit()
        servicer.expire_stale_plans(time.time() - sched.plan_ttl_s)

        alloc = {job: auto.target for job, (auto, _c, _e) in jobs.items()}
        agg_closed = sum(true_speed(j, n) for j, n in alloc.items())
        agg_equal = sum(true_speed(j, start_n) for j in curves)
        latencies = [
            lat
            for (_a, _c, ex) in jobs.values()
            for (_v, _n, lat) in ex.executed
        ]
        counts = servicer.plan_status_counts()
        results["brain_allocation"] = dict(sorted(alloc.items()))
        results["brain_total_chips"] = total_chips
        results["brain_agg_goodput_closed"] = round(agg_closed, 2)
        results["brain_agg_goodput_equal_split"] = round(agg_equal, 2)
        results["brain_goodput_gain_pct"] = round(
            100.0 * (agg_closed / agg_equal - 1.0), 2
        )
        results["brain_decision_to_resized_ms"] = (
            round(statistics.median(latencies), 2) if latencies else None
        )
        results["brain_plans_emitted"] = sum(counts.values())
        results["brain_plans_acked"] = counts.get("acked", 0)
        results["brain_plans_expired"] = counts.get("expired", 0)
        results["brain_plans_superseded"] = counts.get("superseded", 0)
        results["brain_plans_unresolved"] = counts.get("pending", 0)
        # the feedback rows the next pass plans against, visible the
        # same way tools/brain_ctl.py shows them
        results["brain_outcome_rows"] = sum(
            1
            for r in servicer.plan_history()
            if r["decision_to_resized_ms"] is not None
        )
    finally:
        for _auto, cli, _ex in jobs.values():
            cli.close()
        server.stop(grace=1)
        servicer.close()


def run_chaos_bench(jax, results: dict, smoke: bool = False):
    """Deterministic chaos leg (``tools/chaos.py``): scripted
    preemption scenarios with hard recovery gates — ISSUE 11's survival
    contract as CI.

    - **eviction_during_save**: an eviction notice lands while a
      chunked save is staged; the graceful drain must emergency-commit
      the CURRENT step inside the grace window, book the drain to the
      ``eviction`` goodput category (not ``other``), leave a flight
      bundle, and a resumed trainer must reproduce the uninterrupted
      run's losses BITWISE with zero wedged threads;
    - **sigkill_mid_step**: a real trainer subprocess hard-exits
      (``node.preempt:kill:@K``) mid-run; the restart must resume from
      a verified checkpoint losing at most one commit interval of
      steps and stay loss-continuous over the replayed overlap.

    Keys: ``chaos_evict_*`` / ``chaos_kill_*``; ``--smoke`` exits
    nonzero when either scenario's gate fails.
    """
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tools"))
    try:
        import chaos
    finally:
        sys.path.pop(0)

    r = chaos.run_scenario("eviction_during_save", seed=7)
    results["chaos_evict_ok"] = bool(r.get("ok"))
    results["chaos_evict_verified_step"] = r.get("verified_step")
    results["chaos_evict_loss_bitwise"] = r.get("loss_bitwise")
    results["chaos_evict_goodput_eviction_s"] = r.get(
        "goodput_eviction_s"
    )
    results["chaos_evict_drain_ms"] = r.get("drain_ms")
    results["chaos_evict_lost_steps"] = r.get("lost_steps")
    results["chaos_evict_wedged_threads"] = len(
        r.get("wedged_threads", [])
    )

    k = chaos.run_scenario("sigkill_mid_step", seed=7)
    results["chaos_kill_ok"] = bool(k.get("ok"))
    results["chaos_kill_lost_steps"] = k.get("lost_steps")
    results["chaos_kill_commit_interval"] = chaos.COMMIT_INTERVAL
    results["chaos_kill_loss_bitwise"] = k.get("loss_bitwise")


# the SDC gates (ISSUE 20): the tier-1 fence must flag the injected
# chip within this many steps of corruption onset (measured: 1 — the
# cross-lane test needs no history)
SDC_DETECT_STEP_GATE = 10
# extra seeds for the innocent-conviction sweep: with the full
# scenario's seed 7 (lane 3) these cover three distinct injected lanes
SDC_EXTRA_SEEDS = (13, 20)  # lanes 1 and 0


def run_sdc_bench(jax, results: dict, smoke: bool = False):
    """Silent-data-corruption defense leg (ISSUE 20): the chaos
    scenario's full chain plus the two properties a scenario run alone
    cannot gate.

    - **sdc_quarantine** (``tools/chaos.py``): one chip computes
      wrong-but-finite numbers; the fence must detect within
      ``SDC_DETECT_STEP_GATE`` steps of onset, the paired audit must
      convict EXACTLY the injected lane, rollback must land on the
      verified step with the replay booked to ``restart_replay``, the
      convicted rank must be absent from the next frozen rendezvous
      world, and the resumed run must match the golden losses BITWISE.
    - **innocent-conviction sweep**: the convict-only leg re-runs the
      injection under ``SDC_EXTRA_SEEDS`` (different lanes): across
      all three seeds no lane other than the injected one may ever be
      convicted — a defense that shoots bystanders is worse than none.
    - **detector overhead**: the steady-state per-step cost of
      :meth:`SdcDetector.observe` (host-side Python on a handful of
      floats), gated under the tracer-overhead budget
      (``TRACER_OVERHEAD_GATE_PCT`` / ``TRACER_OVERHEAD_FLOOR_MS``) —
      an always-on fence must be too cheap to ever turn off.

    Keys: ``sdc_*``; ``--smoke`` exits nonzero when any gate fails.
    """
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tools"))
    try:
        import chaos
    finally:
        sys.path.pop(0)

    r = chaos.run_scenario("sdc_quarantine", seed=7)
    results["sdc_quarantine_ok"] = bool(r.get("ok"))
    results["sdc_detect_steps"] = r.get("detect_steps")
    results["sdc_convicted_exact"] = bool(
        r.get("convicted") == [r.get("injected_lane")]
    )
    results["sdc_rollback_ok"] = bool(
        r.get("verified_step", -1) >= 0
        and r.get("halted_step") == r.get("verified_step")
        and r.get("resumed_step") == r.get("verified_step")
        and (r.get("goodput_replay_s") or 0) > 0
    )
    results["sdc_loss_bitwise"] = bool(r.get("loss_bitwise"))
    results["sdc_excluded_from_world"] = bool(
        r.get("injected_lane") in r.get("excluded_ranks", [])
        and r.get("injected_lane") not in r.get("world_ranks", [])
        and len(r.get("world_ranks", [])) == 3
    )
    results["sdc_rollback_steps_lost"] = (
        (r.get("detect_step") or 0) - (r.get("verified_step") or 0)
    )

    innocent = r.get("innocent_convictions", 1)
    import tempfile as _tf

    for seed in SDC_EXTRA_SEEDS:
        with _tf.TemporaryDirectory(prefix="dlrover_sdc_bench_") as wd:
            c = chaos.sdc_convict_only(seed, wd)
        innocent += c.get("innocent_convictions", 1)
        if not c.get("ok"):
            results[f"sdc_convict_seed{seed}_ok"] = False
    results["sdc_innocent_convictions"] = innocent
    results["sdc_seeds_swept"] = 1 + len(SDC_EXTRA_SEEDS)

    # steady-state detector cost: clean observations (the common case —
    # every anomaly-free step pays exactly this)
    from dlrover_tpu.parallel.sdc import SdcDetector

    det = SdcDetector(n_lanes=8)
    rng = np.random.default_rng(0)
    lanes = rng.uniform(0.9, 1.1, size=(512, 8))
    for i in range(64):  # warm the window
        det.observe(i, 1.0, lanes[i % 512])
    # best-of-segments (the drift-hardened idiom): the detector's true
    # per-step cost is what the gate prices, not scheduler noise from
    # whatever else the bench process is doing — a single long loop
    # absorbs every preemption that lands inside it
    per_step_s = math.inf
    step = 64
    for _ in range(8):
        t0 = time.perf_counter()
        for i in range(128):
            det.observe(step, 1.0, lanes[(step + i) % 512])
        per_step_s = min(
            per_step_s, (time.perf_counter() - t0) / 128
        )
        step += 128
    results["sdc_detector_overhead_ms"] = round(per_step_s * 1e3, 4)
    # same two-clause budget as the tracer: percentage gate against a
    # smoke-scale step, absolute noise floor below it
    step_s = (results.get("trace_step_ms_off") or 100.0) / 1e3
    overhead_pct = 100.0 * per_step_s / step_s
    results["sdc_detector_overhead_pct"] = round(overhead_pct, 3)
    results["sdc_overhead_ok"] = bool(
        overhead_pct <= TRACER_OVERHEAD_GATE_PCT
        or per_step_s * 1e3 <= TRACER_OVERHEAD_FLOOR_MS
    )


# the sparse-embedding gates (ISSUE 12). Overlap: the device-tier
# pipelined cycle must beat the synchronous host gather→step→scatter
# cycle by at least 5% on the smoke config (measured steady-state
# ratios land ~0.65-0.85; 0.95 is the regression floor, not the
# target). Hit rate: the HBM hot tier must absorb >= 75% of unique-id
# traffic on the zipfian trace once warm (measured ~80%).
SPARSE_OVERLAP_GATE = 0.95
SPARSE_HIT_GATE_PCT = 75.0


def run_sparse_bench(jax, results: dict, smoke: bool = False):
    """TPU-native elastic sparse embeddings (ISSUE 12): the three-tier
    path A/B'd against the host-side cycle it replaces.

    - **overlap on/off**: identical zipfian id streams drive (a) the
      synchronous ``SparseTrainer.train_step`` host cycle and (b) the
      device hot tier + ``SparseRowPipeline`` overlapped cycle;
      interleaved timed segments (drift-hardened like the trace bench),
      per-mode median of the best segment. Gate:
      ``sparse_step_overlap_on_vs_off`` < ``SPARSE_OVERLAP_GATE``.
    - **hot-tier hit rate**: steady-state (post-settle) unique-id hit
      share on the zipfian trace ≥ ``SPARSE_HIT_GATE_PCT``.
    - **warm reshard vs re-import**: ``warm_reshard`` (move only
      re-routed rows, in memory) vs the full npz export→import failover
      path on the same state: ``embedding_reshard_warm_ms`` must beat
      ``embedding_reshard_full_ms``.
    - **chunked-delta resume**: a full+delta chain written through the
      budgeted ``EmbeddingDeltaStager`` (advance between steps) is
      restored into a fresh trainer which replays the tail of the run —
      losses must match the uninterrupted run BITWISE
      (``sparse_resume_bitwise``).
    """
    import jax.numpy as jnp

    from dlrover_tpu.data.sparse_prefetch import SparseRowPipeline
    from dlrover_tpu.ops.embedding import (
        IncrementalCheckpointManager,
        DeviceSparseEmbedding,
        EmbeddingTierStats,
        ShardedKvEmbedding,
    )
    from dlrover_tpu.trainer.sparse import SparseTrainer

    # sized so the host legs the overlap removes are material on the
    # CPU smoke box (8k ids × 512-byte rows ≈ 4 MB/step each way):
    # measured steady ratios 0.69-0.77 vs the 0.95 gate
    DIM, IDS, VOCAB, ZIPF = 128, 8192, 50_000, 1.6
    SETTLE, SEG_STEPS, SEGMENTS = 14, 10, 4

    def dense_factory(lr=0.3):
        @jax.jit
        def loss_fn(w, rows, y):
            p = jax.nn.sigmoid(rows @ w)
            return -jnp.mean(
                y * jnp.log(p + 1e-7) + (1 - y) * jnp.log(1 - p + 1e-7)
            )

        grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))

        def dense_step(w, rows, batch):
            y = jnp.asarray(batch)
            loss, (gw, grows) = grad_fn(w, jnp.asarray(rows), y)
            return w - lr * gw, grows, {"loss": float(loss)}

        return dense_step

    def make_step(s: int):
        r = np.random.default_rng(11 * 100_000 + s)
        ids = np.minimum(r.zipf(ZIPF, IDS), VOCAB).astype(np.int64)
        return ids, (ids % 2).astype(np.float32)

    def stream(start: int, n: int):
        for s in range(start, start + n):
            yield make_step(s)

    # -- leg 1: overlap on/off + hit rate ------------------------------
    host_sync = ShardedKvEmbedding(4, DIM, num_slots=1, seed=0)
    t_sync = SparseTrainer(
        host_sync, jnp.zeros((DIM,)), dense_factory(), sparse_lr=0.1
    )
    host_dev = ShardedKvEmbedding(4, DIM, num_slots=1, seed=0)
    emb = DeviceSparseEmbedding(
        host_dev, capacity=16384, sparse_optimizer="adagrad", lr=0.1
    )
    t_dev = SparseTrainer(
        emb, jnp.zeros((DIM,)), dense_factory(), sparse_lr=0.1
    )

    cursor = {"sync": 0}
    total_dev = SETTLE + SEGMENTS * SEG_STEPS

    def run_sync_steps(n, timed):
        times = []
        for ids, y in stream(cursor["sync"], n):
            t0 = time.perf_counter()
            t_sync.train_step(ids, y)
            times.append(time.perf_counter() - t0)
        cursor["sync"] += n
        return times if timed else []

    # ONE pipeline spans settle + every timed segment: tearing it down
    # per segment would bill each segment's first step a cold prepare
    # (exactly the stall the overlap removes)
    pipe = SparseRowPipeline(stream(0, total_dev), emb)
    dev_iter = iter(pipe)

    def run_dev_steps(n, timed):
        times = []
        for _ in range(n):
            ids, y, prep = next(dev_iter)
            t0 = time.perf_counter()
            t_dev.train_step_device(ids, y, prep)
            times.append(time.perf_counter() - t0)
        return times if timed else []

    try:
        # settle: saturate the hot set, compile every shape bucket
        run_sync_steps(SETTLE, timed=False)
        run_dev_steps(SETTLE, timed=False)
        emb.stats = EmbeddingTierStats()  # steady-state hit accounting

        sync_meds, dev_meds = [], []
        for _ in range(SEGMENTS):  # interleaved: drift balanced
            sync_meds.append(
                float(np.median(run_sync_steps(SEG_STEPS, timed=True)))
            )
            dev_meds.append(
                float(np.median(run_dev_steps(SEG_STEPS, timed=True)))
            )
    finally:
        pipe.close()
    sync_ms = min(sync_meds) * 1e3
    dev_ms = min(dev_meds) * 1e3
    results["sparse_step_sync_ms"] = round(sync_ms, 3)
    results["sparse_step_overlap_ms"] = round(dev_ms, 3)
    results["sparse_step_overlap_on_vs_off"] = round(
        dev_ms / sync_ms, 4
    )
    results["sparse_overlap_gate"] = SPARSE_OVERLAP_GATE
    results["embedding_gather_hit_pct"] = round(emb.stats.hit_pct, 2)
    results["embedding_hit_gate_pct"] = SPARSE_HIT_GATE_PCT
    results["embedding_kernel_mode"] = emb.hot.kernel_mode
    scalars = emb.export_metrics()
    results["embedding_host_leg_ms"] = scalars["emb_host_leg_ms"]
    results["embedding_spill_bytes"] = scalars["emb_spill_bytes"]
    emb.flush()
    emb.close()

    # -- leg 2: warm reshard vs full re-import -------------------------
    ROWS = 20_000 if smoke else 60_000
    store = ShardedKvEmbedding(4, 32, num_slots=1, seed=3)
    store.gather(np.arange(ROWS, dtype=np.int64))
    state0 = store.export_state()
    # the full path is the failover SparseTrainer replaced: export
    # everything, write the npz, read it back, import everything
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "emb.npz")
        np.savez(p, **state0)
        fresh = ShardedKvEmbedding(6, 32, num_slots=1, seed=3)
        fresh.import_state(dict(np.load(p)))
    full_ms = (time.perf_counter() - t0) * 1e3
    report = store.warm_reshard(6)
    warm_ms = report.elapsed_s * 1e3
    results["embedding_reshard_full_ms"] = round(full_ms, 2)
    results["embedding_reshard_warm_ms"] = round(warm_ms, 2)
    results["embedding_reshard_moved_pct"] = round(
        100.0 * report.moved_fraction, 2
    )

    # -- leg 3: chunked-delta bitwise resume ---------------------------
    with tempfile.TemporaryDirectory() as ckpt_dir:
        def new_trainer():
            h = ShardedKvEmbedding(2, DIM, num_slots=1, seed=7)
            e = DeviceSparseEmbedding(
                h, capacity=8192, sparse_optimizer="adagrad", lr=0.2
            )
            return SparseTrainer(
                e, jnp.zeros((DIM,)), dense_factory(), sparse_lr=0.2
            ), h, e

        def resume_stream(start, n, seed=77):
            for s in range(start, start + n):
                r = np.random.default_rng(seed * 1000 + s)
                ids = np.minimum(r.zipf(ZIPF, 512), 4000).astype(
                    np.int64
                )
                yield ids, (ids % 2).astype(np.float32)

        ta, ha, ea = new_trainer()
        mgr_a = IncrementalCheckpointManager(
            ha, ckpt_dir, full_every=4
        )
        losses_a = [
            m["loss"] for m in ta.run(resume_stream(0, 3), overlapped=False)
        ]
        ea.flush()
        mgr_a.save(step=3)  # full
        losses_a += [
            m["loss"] for m in ta.run(resume_stream(3, 2), overlapped=False)
        ]
        ea.flush()
        # dirty-row delta staged in budgeted chunks "between steps"
        stager = mgr_a.begin_chunked_save(step=5, chunk_bytes=64 << 10)
        dense_at_5 = np.asarray(ta.dense_params)
        tail_a = []
        for ids, y in resume_stream(5, 5):
            stager.advance(budget_s=0.002)
            tail_a.append(ta.train_step_device(ids, y)["loss"])
        stager.commit()
        ea.close()

        tb, hb, eb = new_trainer()
        mgr_b = IncrementalCheckpointManager(hb, ckpt_dir)
        restored_step = mgr_b.restore()
        tb.step = restored_step or 0
        tb.dense_params = jnp.asarray(dense_at_5)
        tail_b = [
            m["loss"]
            for m in tb.run(resume_stream(5, 5), overlapped=False)
        ]
        eb.close()
        results["sparse_resume_restored_step"] = restored_step
        results["sparse_resume_bitwise"] = bool(
            restored_step == 5 and tail_a == tail_b
        )
        results["sparse_resume_tail_gap"] = float(
            max(
                abs(a - b) for a, b in zip(tail_a, tail_b)
            )
        )


# -- mesh-matrix gates (ISSUE 13) -------------------------------------------
# fp32 parity of the explicit pp step against the plain-dp reference
# model (same params, same batch, 4 optimizer steps) — the fully-manual
# region reduces in a different order than GSPMD's dp schedule, so the
# gate is float-noise-tight rather than bitwise (measured ~5e-7)
MESH_PP_PARITY_GATE = 1e-4
# tp-containing meshes (3d): same rationale as HYBRID_TP_PARITY_GATE
MESH_3D_PARITY_GATE = 1e-5


def run_mesh_matrix_bench(jax, results: dict, smoke: bool = False):
    """The ISSUE 13 acceptance legs — the mesh matrix is finished when
    every axis combination the strategy search emits takes the
    explicit sync path:

    - **pp** (pp2 x dp4, gpipe): the explicit per-stage
      bubble-scheduled sync trains within ``MESH_PP_PARITY_GATE`` of
      a plain dp=8 reference from the same params (on this jaxlib the
      GSPMD pipeline step itself cannot run — partial-manual needs
      PartitionId SPMD support — which is exactly why the fully-manual
      explicit region earns its keep), and the dry-runner prices its
      ``comm_exposed`` strictly below the post-drain monolithic
      fallback (the bubble absorbs the wire time);
    - **ep** (dp2 x ep2 MoE): explicit-path parity with GSPMD, and the
      capacity rebalance cuts the overflow-drop rate on a skewed
      routing workload vs the static uniform capacity;
    - **3D** (dp2 x fsdp2 x tp2): explicit-path parity within
      ``MESH_3D_PARITY_GATE`` and wire bytes <= the PR-8 dp x fsdp
      plan (tp adds no dp-leg bytes);
    - **micro-batch rebalance** (6-of-8 at batch 32): the trainer's
      resize picks the padded all-ranks strategy
      (``resize_idle_ranks`` = 0, ``resize_mb_pad`` = 4) and the
      per-rank critical path — timed on one device, since the virtual
      CPU backend timeshares a single host and total wall time would
      charge the pads to the wrong side — yields higher aggregate
      throughput than idling 2 ranks.
    """
    import optax

    from dlrover_tpu.accel.dry_runner import (
        DryRunReport,
        _analytic_estimate,
        _comm_estimate,
    )
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.train import (
        TrainState,
        build_train_step,
        init_sharded_state,
        shard_batch,
    )
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.grad_sync import (
        plan_for_mesh,
        plan_for_pipeline,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.pipeline import (
        build_pipeline_train_step,
        pipeline_state_shardings,
        stack_pipeline_params,
    )

    import jax.numpy as jnp

    devs = list(jax.devices())
    if len(devs) < 8:
        results["mesh_matrix_error"] = (
            f"mesh matrix bench needs >= 8 devices, have {len(devs)}"
        )
        return
    cfg = tiny(num_layers=2)
    cfg = replace(cfg, dtype="float32", param_dtype="float32")
    tx = optax.adamw(1e-2)
    steps = 4 if smoke else 8
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    xj = jnp.asarray(x)
    params0 = init_params(jax.random.PRNGKey(0), cfg)

    # -- leg 1: pp explicit vs plain-dp reference -----------------------
    mesh_ref = build_mesh(MeshConfig(dp=8), devices=devs)
    state_r = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params0,
        opt_state=tx.init(params0),
    )
    step_r = build_train_step(cfg, mesh_ref, tx, donate=False)
    b = shard_batch({"x": x, "y": x}, mesh_ref)
    for _ in range(steps):
        state_r, mr = step_r(state_r, b["x"], b["y"])
    loss_ref = float(mr["loss"])

    mc_pp = MeshConfig(pp=2, dp=4)
    pp_plan = plan_for_pipeline(cfg, mc_pp.axis_sizes(), grad_bucket_mb=1)
    results["mesh_matrix_pp_path"] = (
        "explicit" if pp_plan is not None else "gspmd"
    )
    mesh_pp = build_mesh(mc_pp, devices=devs)
    sh = pipeline_state_shardings(cfg, mesh_pp, tx)
    stacked = jax.device_put(
        stack_pipeline_params(params0, 2), sh.params
    )
    state_pp = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=stacked,
        opt_state=jax.device_put(tx.init(stacked), sh.opt_state),
    )
    step_pp = build_pipeline_train_step(
        cfg, mesh_pp, tx, 2, donate=False, schedule="gpipe",
        comm_overlap=True, grad_bucket_mb=1,
    )
    for _ in range(steps):
        state_pp, mp = step_pp(state_pp, xj, xj)
    loss_pp = float(mp["loss"])
    results["mesh_matrix_pp_loss_ref"] = round(loss_ref, 6)
    results["mesh_matrix_pp_loss_explicit"] = round(loss_pp, 6)
    results["mesh_matrix_pp_parity"] = bool(
        abs(loss_pp - loss_ref) <= MESH_PP_PARITY_GATE
    )

    # dry-runner comm exposure: bubble-scheduled explicit vs the
    # post-drain monolithic fallback of the SAME mesh
    def _exposed(s):
        r = DryRunReport(strategy=s, ok=False)
        _analytic_estimate(r, cfg, 8, 32, devs)
        _comm_estimate(r, cfg, 8, 32, devs)
        return r.comm_exposed_s

    s_pp = Strategy(
        mesh=mc_pp, num_microbatches=2, comm_overlap=True,
        dtype="float32",
    )
    exp_explicit = _exposed(s_pp)
    exp_fallback = _exposed(replace(s_pp, comm_overlap=False))
    results["mesh_matrix_pp_comm_exposed_ratio"] = round(
        exp_explicit / max(exp_fallback, 1e-12), 4
    )

    # -- leg 2: ep explicit parity + capacity rebalance ------------------
    cfg_moe = replace(cfg, num_experts=2)

    def run_ep(comm_overlap):
        mesh = build_mesh(MeshConfig(dp=2, ep=2), devices=devs[:4])
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg_moe, mesh, tx
        )
        step = build_train_step(
            cfg_moe, mesh, tx, donate=False,
            comm_overlap=comm_overlap, grad_bucket_mb=1,
        )
        bb = shard_batch({"x": x, "y": x}, mesh)
        for _ in range(steps):
            state, m = step(state, bb["x"], bb["y"])
        return float(m["loss"])

    ep_plan = plan_for_mesh(
        cfg_moe,
        build_mesh(MeshConfig(dp=2, ep=2), devices=devs[:4]),
        grad_bucket_mb=1,
    )
    results["mesh_matrix_ep_path"] = (
        "explicit" if ep_plan is not None else "gspmd"
    )
    l_gspmd = run_ep(False)
    l_expl = run_ep(True)
    results["mesh_matrix_ep_loss_gap"] = round(
        abs(l_expl - l_gspmd), 6
    )
    results["mesh_matrix_ep_parity"] = bool(
        abs(l_expl - l_gspmd) <= MESH_3D_PARITY_GATE
    )

    # capacity rebalance on a skewed routing workload: static uniform
    # capacity vs the re-split the measured load produces
    from dlrover_tpu.parallel.moe import (
        CapacityRebalancer,
        topk_gating,
    )

    T, E = 512, 4
    logits = np.random.default_rng(1).standard_normal(
        (T, E)
    ).astype(np.float32)
    logits[:, 0] += 1.5  # hot expert
    logits_j = jnp.asarray(logits)
    base_cap = int(1.25 * T / E)
    _, _, _, _, st0 = topk_gating(
        logits_j, E, base_cap, k=1, return_stats=True
    )
    drop_static = float(st0["drop"])
    reb = CapacityRebalancer(E, capacity_factor=1.25, ema=0.0)
    reb.observe(np.asarray(st0["load"]))
    caps = reb.splits(T)
    _, _, _, _, st1 = topk_gating(
        logits_j, E, max(caps), k=1,
        expert_caps=jnp.asarray(caps, jnp.float32),
        return_stats=True,
    )
    drop_reb = float(st1["drop"])
    results["mesh_matrix_ep_drop_static"] = round(drop_static, 4)
    results["mesh_matrix_ep_drop_rebalanced"] = round(drop_reb, 4)
    results["mesh_matrix_ep_caps"] = list(caps)

    # -- leg 3: 3D parity + wire bytes ----------------------------------
    def run_3d(comm_overlap):
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2, tp=2), devices=devs
        )
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_train_step(
            cfg, mesh, tx, donate=False,
            comm_overlap=comm_overlap, grad_bucket_mb=1,
        )
        bb = shard_batch({"x": x, "y": x}, mesh)
        for _ in range(steps):
            state, m = step(state, bb["x"], bb["y"])
        return float(m["loss"])

    mesh_3d = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=devs)
    plan_3d = plan_for_mesh(cfg, mesh_3d, grad_bucket_mb=64)
    plan_fsdp = plan_for_mesh(
        cfg,
        build_mesh(MeshConfig(dp=2, fsdp=2), devices=devs[:4]),
        grad_bucket_mb=64,
    )
    results["mesh_matrix_3d_path"] = (
        "explicit" if plan_3d is not None else "gspmd"
    )
    l3_gspmd = run_3d(False)
    l3_expl = run_3d(True)
    results["mesh_matrix_3d_loss_gap"] = round(
        abs(l3_expl - l3_gspmd), 7
    )
    results["mesh_matrix_3d_parity"] = bool(
        abs(l3_expl - l3_gspmd) <= MESH_3D_PARITY_GATE
    )
    results["mesh_matrix_3d_wire_bytes"] = plan_3d.explicit_wire_bytes()
    results["mesh_matrix_3d_wire_vs_fsdp"] = round(
        plan_3d.explicit_wire_bytes()
        / max(plan_fsdp.explicit_wire_bytes(), 1),
        4,
    )

    # -- leg 4: micro-batch rebalance on 6-of-8 -------------------------
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    class _Tokens:
        def __init__(self, n=2048, seq=32, vocab=256):
            r = np.random.default_rng(0)
            self.data = r.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    trainer = ElasticTrainer(
        model_cfg=replace(cfg, num_layers=1) if smoke else cfg,
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=32,
            seq_len=32,
            report_metrics=False,
            log_interval=1000,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
            comm_overlap=True,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=8), dtype="float32"),
        devices=devs,
    )
    try:
        trainer.train(num_steps=3)  # calibrates the rebalance pricing
        trainer.resize(6)
        s6 = trainer.accel.strategy
        results["mesh_matrix_mb_pad"] = s6.batch_pad
        results["mesh_matrix_mb_idle_ranks"] = (
            trainer.pipeline_stats.resize_idle_ranks
        )
        results["mesh_matrix_mb_strategy"] = s6.describe()
        trainer.train(num_steps=6)  # the padded world actually trains
        results["mesh_matrix_mb_steps"] = int(trainer.global_step)
    finally:
        trainer.close()

    # aggregate-throughput A/B on the per-rank critical path: the
    # virtual CPU backend timeshares ONE host, so wall time scales
    # with TOTAL rows and would charge the pads to the wrong side —
    # real hardware runs ranks in parallel, so the step's critical
    # path is one rank's rows. Time those on a single device.
    cfg_t = replace(cfg, num_layers=1) if smoke else cfg
    mesh1 = build_mesh(MeshConfig(dp=1), devices=devs[:1])
    state1, _ = init_sharded_state(
        jax.random.PRNGKey(0), cfg_t, mesh1, tx
    )
    step1 = build_train_step(cfg_t, mesh1, tx, donate=False)

    def rank_step_ms(rows):
        xb = rng.integers(
            0, cfg_t.vocab_size, (rows, 32)
        ).astype(np.int32)
        bb = shard_batch({"x": xb, "y": xb}, mesh1)
        st, _ = step1(state1, bb["x"], bb["y"])  # compile+warm
        jax.block_until_ready(st.params)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            st, _ = step1(state1, bb["x"], bb["y"])
            jax.block_until_ready(st.params)
            times.append(time.perf_counter() - t0)
        return float(np.median(times) * 1e3)

    idle_rows = 32 // 4  # dp4 idle path: 8 rows/rank
    reb_rows = (32 + results.get("mesh_matrix_mb_pad", 4)) // 6
    t_idle = rank_step_ms(idle_rows)
    t_reb = rank_step_ms(reb_rows)
    results["mesh_matrix_mb_rank_ms_idle"] = round(t_idle, 3)
    results["mesh_matrix_mb_rank_ms_rebalanced"] = round(t_reb, 3)
    # samples/sec: both paths retire 32 REAL samples per step
    results["mesh_matrix_mb_throughput_gain"] = round(
        t_idle / max(t_reb, 1e-9), 4
    )
    results["mesh_matrix_note"] = (
        "pp2xdp4 bubble-scheduled sync, dp2xep2 manual-region "
        "all-to-alls + capacity rebalance, dp2xfsdp2xtp2 composed "
        "ZeRO+tp, 6-of-8 micro-batch rebalance (pad 4 rows, 6 ranks "
        "x 6 rows vs 4 ranks x 8 rows)"
    )


# control-plane gates (ISSUE 14): steady-state RPC fan-in, delta wire
# compression, loopback p99, and the multi-path overlap A/B.
# p99 is generous for a loopback call because CI boxes timeshare — the
# number that matters is the ORDER (sub-second for a 1k-node tick);
# the load harness's own CLI gates tighter on quiet hardware.
CONTROL_PLANE_RPC_GATE = 1.25          # RPCs/node/tick, steady state
CONTROL_PLANE_DELTA_GATE = 0.4         # delta bytes / full-payload bytes
CONTROL_PLANE_P99_GATE_MS = 500.0      # loopback client-observed p99

# striped effective GB/s over the emulated 2.0+1.0 GB/s two-rail link
# vs the best single rail: the ideal completion-time-balanced split
# yields 1.5x; 1.3 leaves headroom for thread scheduling noise
MULTIRAIL_SPEEDUP_GATE = 1.3


def _transfer_overlap_ab(steps=6, compute_s=0.04, chunks=4,
                         chunk_s=0.003):
    """Step-blocked host-transfer time, arbitrated vs serialized, on a
    simulated workload: per run, TWO streams (a background checkpoint
    stage and a backpressure spill) must each land ``steps * chunks``
    transfers of ``chunk_s``.

    - serialized (the pre-arbiter world): every transfer runs inline in
      the inter-step host section — all of it is step-blocked;
    - arbitrated: the streams run on their own threads acquiring link
      grants while the trainer marks compute windows — transfers land
      under compute and only the tail past the last step is blocked.

    Returns ``(blocked_arb_ms, blocked_serial_ms)``. Transfers are
    sleeps (the link physics, not the payload): the A/B isolates the
    SCHEDULING, and the bitwise gates elsewhere in --smoke prove the
    arbiter never touches contents."""
    import threading

    from dlrover_tpu.parallel.transfer_sched import (
        Priority,
        TransferArbiter,
    )

    total_transfers = steps * chunks

    # serialized baseline
    t0 = time.perf_counter()
    for _ in range(steps):
        time.sleep(compute_s)
        for _ in range(chunks):
            time.sleep(chunk_s)  # ckpt chunk, inline
            time.sleep(chunk_s)  # spill, queued behind it
    wall_serial = time.perf_counter() - t0
    blocked_serial = wall_serial - steps * compute_s

    # arbitrated: same total work, scheduled into compute windows
    arb = TransferArbiter(aging_s=1.0, enabled=True)
    ckpt = arb.register("ckpt", Priority.BACKGROUND, "d2h")
    spill = arb.register("spill", Priority.BACKPRESSURE, "d2h")

    def worker(stream):
        for _ in range(total_transfers):
            with stream.transfer(1 << 20):
                time.sleep(chunk_s)

    threads = [
        threading.Thread(target=worker, args=(s,), daemon=True)
        for s in (ckpt, spill)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for _ in range(steps):
        arb.note_compute(True)
        time.sleep(compute_s)
        arb.note_compute(False)
    for t in threads:
        t.join()
    wall_arb = time.perf_counter() - t0
    arb.shutdown()
    blocked_arb = wall_arb - steps * compute_s
    return max(blocked_arb, 0.0) * 1e3, max(blocked_serial, 0.0) * 1e3


def run_control_plane_bench(jax, results: dict, smoke: bool = False):
    """The ISSUE 14 acceptance legs (docs/control-plane.md):

    - **load harness** (``tools/rpc_load.py``): 1k fake nodes (2k on
      the full bench; 10k is the harness's own slow tier) against a
      real gRPC master — steady-state RPCs/node/tick must stay ≤
      ``CONTROL_PLANE_RPC_GATE``, delta wire bytes ≤
      ``CONTROL_PLANE_DELTA_GATE`` × the full-payload baseline **at
      identical reconstructed master-side scalars**, client p99 under
      the loopback gate;
    - **failover drill**: the master's delta state wiped mid-run —
      every node resyncs and reconstruction converges;
    - **multi-path overlap**: checkpoint staging + embedding spill
      running concurrently under the arbiter expose strictly less
      step-blocked time than serialized transfers (the
      ``stage_sync_block_ms``-style A/B);
    - **host-leg pricing**: the dry-runner's aggregate host term is
      live — scheduled pricing strictly below serialized, both > 0
      when demand is registered.
    """
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tools"))
    from rpc_load import run_load

    from dlrover_tpu.parallel.transfer_sched import (
        TransferArbiter,
        aggregate_host_exposed_s,
    )

    nodes = 1000 if smoke else 2000
    ticks = 6
    delta = run_load(
        nodes=nodes, ticks=ticks, nscalars=60, churn=0.1, mode="delta"
    )
    full = run_load(
        nodes=nodes, ticks=ticks, nscalars=60, churn=0.1, mode="full"
    )
    results["control_plane_nodes"] = nodes
    results["control_plane_rpcs_per_node_tick"] = delta[
        "rpcs_per_node_per_tick"
    ]
    results["control_plane_rpc_p99_ms"] = delta["rpc_p99_ms"]
    results["control_plane_master_s_per_tick"] = delta[
        "master_service_s_per_tick"
    ]
    results["control_plane_delta_vs_full_bytes"] = round(
        delta["wire_bytes_total"] / max(full["wire_bytes_total"], 1), 4
    )
    results["control_plane_reconstructed_ok"] = bool(
        delta["reconstructed_ok"] and full["reconstructed_ok"]
    )
    # failover drill (small fleet: the property is protocol-level)
    drill = run_load(
        nodes=64, ticks=4, nscalars=60, churn=0.1, mode="delta",
        master_restart_tick=2,
    )
    results["control_plane_resync_converged"] = bool(
        drill["reconstructed_ok"] and drill["resyncs"] > 0
    )
    # multi-path overlap A/B
    blocked_arb, blocked_serial = _transfer_overlap_ab()
    results["transfer_blocked_ms_arbitrated"] = round(blocked_arb, 1)
    results["transfer_blocked_ms_serialized"] = round(blocked_serial, 1)
    # dry-runner host-leg pricing sensitivity
    arb = TransferArbiter(enabled=True)
    arb.set_demand("ckpt_stage", 64 << 20, direction="d2h")
    arb.set_demand("emb_fault", 8 << 20, direction="h2d")
    sched_s = aggregate_host_exposed_s(arbiter=arb)
    arb.shutdown()  # serialized pricing: all of it exposed
    serial_s = aggregate_host_exposed_s(arbiter=arb)
    results["control_plane_host_sched_ms"] = round(sched_s * 1e3, 3)
    results["control_plane_host_serial_ms"] = round(serial_s * 1e3, 3)
    results["control_plane_host_priced"] = bool(
        0.0 < sched_s < serial_s
    )


def run_multirail_bench(jax, results: dict, smoke: bool = False):
    """The ISSUE 16 acceptance legs (docs/performance.md round 16):

    - **striped throughput**: a 256 MiB payload striped across an
      emulated two-rail link (2.0 + 1.0 GB/s, sleep movers priced by
      ``rail_gbps``) must move at ≥ ``MULTIRAIL_SPEEDUP_GATE`` × the
      best single rail's effective bandwidth — completion-time-balanced
      shares, not a fair split;
    - **crc parity**: a real payload striped into a scratch buffer must
      land byte-identical with the ``crc32_combine``-folded digest
      equal to the single-pass ``zlib.crc32`` — the wire gate every
      striped mover (ckpt staging, reshard, spill) relies on;
    - **calibration cache**: a cold hidden-fraction A/B must write the
      per-rail measured values under the device fingerprint and a warm
      call must serve them from the cache (measured_at equality);
      pricing must then use the measured fraction, not the documented
      constant.
    """
    import tempfile
    import zlib as _zlib

    import numpy as np

    from dlrover_tpu.parallel import transfer_sched
    from dlrover_tpu.parallel.transfer_sched import (
        StripedTransfer,
        TransferArbiter,
        aggregate_host_exposed_s,
    )

    nbytes = (256 << 20) if smoke else (1 << 30)
    arb = TransferArbiter(enabled=True)
    arb.register_rail("railA", direction="d2h", gbps=2.0)
    arb.register_rail("railB", direction="d2h", gbps=1.0)
    gbps = {"railA": 2.0, "railB": 1.0}

    def sleep_mover(rail, off, ln):
        # the link physics, not the payload: wall time IS the
        # emulated wire time, so effective GB/s falls out directly
        time.sleep(ln / (gbps[rail] * 1e9))

    both = StripedTransfer(
        arb, name="mr_bench", direction="d2h",
        chunk_bytes=32 << 20, rails=["railA", "railB"],
        ignore_window=True,
    )
    rep = both.run(sleep_mover, nbytes=nbytes)
    single = StripedTransfer(
        arb, name="mr_bench", direction="d2h",
        chunk_bytes=32 << 20, rails=["railA"], ignore_window=True,
    )
    rep1 = single.run(sleep_mover, nbytes=nbytes)
    eff_both = rep.effective_gbps()
    eff_single = rep1.effective_gbps()
    results["multirail_effective_GBps"] = round(eff_both, 3)
    results["multirail_single_rail_GBps"] = round(eff_single, 3)
    results["multirail_effective_GBps_vs_single"] = round(
        eff_both / max(eff_single, 1e-9), 3
    )
    results["multirail_stripe_balance_pct"] = round(
        rep.balance * 100.0, 1
    )

    # crc parity on a real payload: striped bytes land bitwise and the
    # folded digest equals the one-pass crc
    payload = np.frombuffer(
        np.random.default_rng(16).bytes(8 << 20), dtype=np.uint8
    )
    dest = np.zeros_like(payload)

    def copy_mover(rail, off, ln):
        dest[off:off + ln] = payload[off:off + ln]

    crc_striper = StripedTransfer(
        arb, name="mr_bench", direction="d2h",
        chunk_bytes=1 << 20, rails=["railA", "railB"],
        ignore_window=True,
    )
    crep = crc_striper.run(copy_mover, payload=payload)
    parity = (
        crep.crc32 == _zlib.crc32(payload)
        and dest.tobytes() == payload.tobytes()
    )
    results["stripe_crc_parity"] = "bitwise" if parity else "mismatch"
    arb.shutdown()

    # calibration: cold measure -> cache -> warm hit -> measured pricing
    with tempfile.TemporaryDirectory() as tmp:
        transfer_sched.reset_calibration()
        cold = transfer_sched.calibrate_hidden_fraction(cache_dir=tmp)
        transfer_sched.reset_calibration()
        warm = transfer_sched.calibrate_hidden_fraction(cache_dir=tmp)
        results["arbiter_calibration_cache_hit"] = bool(
            warm.measured_at == cold.measured_at
        )
        results["arbiter_hidden_fraction_measured"] = {
            r: round(v, 4) for r, v in warm.hidden_fraction.items()
        }
        # measured pricing: with the calibration installed the
        # scheduled host term must use the measured fraction (compare
        # against the hand-computed per-direction max)
        from dlrover_tpu.parallel.topology import price_host_transfer

        pa = TransferArbiter(enabled=True)
        pa.set_demand("ckpt_stage", 64 << 20, direction="d2h")
        pa.set_demand("emb_fault", 8 << 20, direction="h2d")
        sched = aggregate_host_exposed_s(arbiter=pa, calibration=warm)
        want = max(
            price_host_transfer(64 << 20, h2d=False)
            * (1.0 - transfer_sched.hidden_fraction_for(
                "host_d2h", warm
            )),
            price_host_transfer(8 << 20, h2d=True)
            * (1.0 - transfer_sched.hidden_fraction_for(
                "host_h2d", warm
            )),
        )
        pa.shutdown()
        results["multirail_priced_from_measured"] = bool(
            abs(sched - want) <= 1e-12 + 1e-6 * want
        )
    transfer_sched.reset_calibration()


# serving co-location gates (ISSUE 17): training goodput may lose at
# most this much (relative %) to a co-located serving plane, and when
# serving is confined to idle gaps the fleet goodput number must stay
# within this many percentage points of the serving-free baseline
SERVING_GOODPUT_LOSS_GATE_PCT = 10.0
SERVING_GAP_DELTA_GATE_PCT = 1.0


def run_serving_bench(jax, results: dict, smoke: bool = False):
    """The ISSUE 17 acceptance legs (serve-while-training):

    - **zero-copy subscribe**: the subscriber's mapped records must
      alias its own shm mapping — no host memcpy on the subscribe path
      (``np.shares_memory`` against the segment buffer);
    - **bitwise decode**: tokens served by the engine over the
      subscribed (crc-gated) frame must be bitwise-identical to a
      greedy decode under a direct step-N restore
      (``load_records(copy=True, verify=True)`` → ``restore_state``);
    - **torn frame**: a commit provoked mid-read (the
      ``serve.stale_read`` delay widens the map→recheck window while a
      thread commits into it) must be caught by the generation
      re-check — never handed out — and the next poll must adopt the
      racing commit cleanly;
    - **co-located goodput**: a simulated train loop (compute spans +
      arbiter marks) with the serving thread soaking its idle gaps
      must lose ≤ ``SERVING_GOODPUT_LOSS_GATE_PCT`` goodput relative
      to the serving-free baseline while tokens/s > 0 and the
      ``serving_soak`` seconds are visible in the ledger; gap-confined
      serving must leave the goodput number within
      ``SERVING_GAP_DELTA_GATE_PCT`` points of the baseline.
    """
    import threading

    from dlrover_tpu.common import faults
    from dlrover_tpu.ckpt.sharding import host_shard_records, restore_state
    from dlrover_tpu.ckpt.shm_handler import ShmHandler, ShmSubscriber
    from dlrover_tpu.models import tiny
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.obs import goodput as obs_goodput
    from dlrover_tpu.obs.goodput import GoodputLedger
    from dlrover_tpu.obs.trace import SpanTracer
    from dlrover_tpu.parallel import transfer_sched
    from dlrover_tpu.rl.continuous_batching import continuous_generate
    from dlrover_tpu.serve import ServingConfig, ServingEngine

    rank = 91  # own shm segment + meta socket; no collision with chaos
    cfg = tiny(vocab_size=31, num_layers=1, max_seq_len=32)
    params = jax.jit(lambda k: init_params(k, cfg))(
        jax.random.PRNGKey(17)
    )
    zeros = jax.tree_util.tree_map(
        lambda a: jax.numpy.zeros_like(a), params
    )
    rng = np.random.default_rng(17)
    n, p_max = 3, 6
    lens = rng.integers(2, p_max + 1, size=n).astype(np.int32)
    toks = np.zeros((n, p_max), np.int32)
    for i, ln in enumerate(lens):
        toks[i, :ln] = rng.integers(1, cfg.vocab_size, size=ln)
    prompts = jax.numpy.asarray(toks)
    plens = jax.numpy.asarray(lens)

    writer = ShmHandler(rank, create=True)
    sub = ShmSubscriber(rank)  # verify=True: every map is crc-gated
    scfg = ServingConfig(max_new_tokens=4, slots=2, soak="idle_gaps")
    eng = ServingEngine(cfg, ShmSubscriber(rank), zeros, scfg)
    try:
        # a stale in-compute mark from an earlier leg would make the
        # first gap-gated batches wait out their timeout
        transfer_sched.note_compute(False)

        # -- zero-copy subscribe ------------------------------------
        writer.save_records(1, host_shard_records(params), {})
        frame = sub.poll()
        seg = np.frombuffer(sub.handler._shm.buf, dtype=np.uint8)
        results["serving_zero_copy"] = bool(
            frame is not None
            and all(np.shares_memory(r.data, seg) for r in frame.records)
        )
        del frame, seg

        # -- bitwise decode vs a direct step-N restore --------------
        assert eng.try_swap()
        key = jax.random.PRNGKey(0)
        got = eng.serve_batch(prompts, plens, key)
        _, recs, _ = writer.load_records(copy=True, verify=True)
        by_path = {r.path: [r] for r in recs}
        direct = restore_state(zeros, lambda p: by_path.get(p, []))
        want = continuous_generate(
            direct, prompts, plens, key, cfg,
            max_new_tokens=scfg.max_new_tokens, eos_id=scfg.eos_id,
            slots=scfg.slots, greedy=True,
        )
        results["serving_bitwise_vs_restore"] = bool(
            all(
                np.array_equal(np.asarray(g), np.asarray(w))
                for g, w in zip(got, want)
            )
        )

        # -- torn frame: commit provoked mid-read -------------------
        writer.save_records(2, host_shard_records(params), {})
        faults.configure("serve.stale_read:delay:1.0")
        committed = threading.Event()

        def racing_commit():
            time.sleep(0.02)  # inside the widened map→recheck window
            writer.save_records(3, host_shard_records(params), {})
            committed.set()

        t = threading.Thread(target=racing_commit)
        t.start()
        torn_frame = sub.poll()
        t.join()
        faults.reset()
        results["serving_torn_provoked"] = bool(committed.is_set())
        recovered = sub.poll()
        results["serving_torn_caught"] = bool(
            torn_frame is None
            and sub.torn_retries >= 1
            and recovered is not None
            and recovered.step == 3
        )
        del torn_frame, recovered

        # -- co-located goodput -------------------------------------
        # warm the decode compile outside the measured windows (marks
        # are idle here, so the gap gate opens immediately)
        eng.try_swap()
        eng.serve_batch(prompts, plens, key)

        steps = 12 if smoke else 40
        compute_s, gap_s = 0.03, 0.02

        def train_loop(tracer):
            for _ in range(steps):
                transfer_sched.note_compute(True)
                with tracer.span("compute"):
                    time.sleep(compute_s)
                transfer_sched.note_compute(False)
                time.sleep(gap_s)

        tr_base = SpanTracer(enabled=True)
        led_base = GoodputLedger(tracer=tr_base)
        train_loop(tr_base)
        base = led_base.snapshot()

        tr_colo = SpanTracer(enabled=True)
        led_colo = GoodputLedger(tracer=tr_colo)
        prev_ledger = obs_goodput.default_ledger()
        obs_goodput.install_default_ledger(led_colo)
        stop = threading.Event()
        served = {"batches": 0, "tokens": 0}

        def serve_loop():
            k = 1
            while not stop.is_set():
                eng.try_swap()
                _, _, out_lens = eng.serve_batch(
                    prompts, plens, jax.random.PRNGKey(k)
                )
                k += 1
                served["batches"] += 1
                served["tokens"] += int(
                    np.sum(np.asarray(out_lens) - lens)
                )

        worker = threading.Thread(target=serve_loop)
        t0 = time.perf_counter()
        worker.start()
        try:
            train_loop(tr_colo)
        finally:
            stop.set()
            worker.join()
            obs_goodput._default = prev_ledger
            transfer_sched.note_compute(False)
        dt = time.perf_counter() - t0
        colo = led_colo.snapshot()

        results["serving_batches"] = served["batches"]
        results["serving_tokens_per_s"] = round(
            served["tokens"] / max(dt, 1e-9), 1
        )
        results["serving_soak_s"] = round(
            colo.seconds.get("serving_soak", 0.0), 6
        )
        results["serving_goodput_base_pct"] = round(base.goodput_pct, 3)
        results["serving_goodput_colocated_pct"] = round(
            colo.goodput_pct, 3
        )
        results["serving_goodput_loss_pct"] = round(
            100.0
            * max(0.0, base.goodput_pct - colo.goodput_pct)
            / max(base.goodput_pct, 1e-9),
            3,
        )
        # gap-confined serving must not move the fleet number: the
        # soak only claims seconds every training row left unclaimed
        results["serving_gap_confined_goodput_delta_pct"] = round(
            colo.goodput_pct - base.goodput_pct, 3
        )
        results["serving_swap_ms"] = eng.stats()["last_swap_ms"]
        results["serving_weight_staleness_steps"] = eng.staleness_steps()
    finally:
        faults.reset()
        sub.close()
        eng.subscriber.close()
        writer.close(unlink=True)


def run_graftlint_gate(results: dict):
    """Static-analysis gate (ISSUE 15): the tree must be graftlint-clean
    — zero unsuppressed findings over ``dlrover_tpu/`` + ``tools/``
    (every suppression carries a reason by construction: a reasonless
    one is itself a finding). Consumes the ``--json`` output so the
    bench artifact records the counts next to the perf keys."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json"],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    payload = json.loads(proc.stdout)
    results["graftlint_unsuppressed"] = payload["unsuppressed"]
    results["graftlint_suppressed"] = payload["suppressed"]
    results["graftlint_clean"] = (
        proc.returncode == 0 and payload["unsuppressed"] == 0
    )
    if not results["graftlint_clean"]:
        # surface the first few findings in the bench artifact so the
        # CI log names the regression without a second run
        results["graftlint_findings"] = [
            f"{f['path']}:{f['line']}: [{f['checker']}] {f['message']}"
            for f in payload["findings"]
            if not f["suppressed"]
        ][:10]


def run_smoke() -> int:
    """Fast CPU-only pass over the pipeline + resize keys (CI wiring:
    overlap and resize-fast-path regressions must fail loudly without a
    30-minute accelerator run). Prints the same JSON shape as the full
    bench, pipeline/resize keys only."""
    import jax

    # the resize leg scales a mesh 4 -> 2 -> 4, so the smoke run needs
    # fake devices: force an 8-device virtual CPU backend (works as
    # long as the backend has not been created yet — this is the first
    # device touch in a --smoke process)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    results: dict = {"mode": "smoke", "platform": "cpu"}
    try:
        run_pipeline_bench(jax, results, smoke=True)
    except Exception as e:
        results["pipeline_error"] = repr(e)
    try:
        run_resize_bench(jax, results, smoke=True)
    except Exception as e:
        results["resize_error"] = repr(e)
    try:
        run_grad_sync_bench(jax, results, smoke=True)
    except Exception as e:
        results["grad_sync_error"] = repr(e)
    try:
        run_topology_bench(jax, results, smoke=True)
    except Exception as e:
        results["topology_error"] = repr(e)
    try:
        run_sparse_sync_bench(jax, results, smoke=True)
    except Exception as e:
        results["sparse_sync_error"] = repr(e)
    try:
        run_hybrid_sync_bench(jax, results, smoke=True)
    except Exception as e:
        results["hybrid_sync_error"] = repr(e)
    try:
        run_trace_bench(jax, results, smoke=True)
    except Exception as e:
        results["trace_error"] = repr(e)
    try:
        run_audit_bench(jax, results, smoke=True)
    except Exception as e:
        results["audit_error"] = repr(e)
    try:
        run_recovery_bench(jax, results, smoke=True)
    except Exception as e:
        results["recovery_error"] = repr(e)
    try:
        run_forensics_bench(jax, results, smoke=True)
    except Exception as e:
        results["forensics_error"] = repr(e)
    try:
        run_brain_bench(jax, results, smoke=True)
    except Exception as e:
        results["brain_error"] = repr(e)
    try:
        run_chaos_bench(jax, results, smoke=True)
    except Exception as e:
        results["chaos_error"] = repr(e)
    try:
        run_sdc_bench(jax, results, smoke=True)
    except Exception as e:
        results["sdc_error"] = repr(e)
    try:
        run_sparse_bench(jax, results, smoke=True)
    except Exception as e:
        results["sparse_error"] = repr(e)
    try:
        run_mesh_matrix_bench(jax, results, smoke=True)
    except Exception as e:
        results["mesh_matrix_error"] = repr(e)
    try:
        run_control_plane_bench(jax, results, smoke=True)
    except Exception as e:
        results["control_plane_error"] = repr(e)
    try:
        run_multirail_bench(jax, results, smoke=True)
    except Exception as e:
        results["multirail_error"] = repr(e)
    try:
        run_serving_bench(jax, results, smoke=True)
    except Exception as e:
        results["serving_error"] = repr(e)
    try:
        run_graftlint_gate(results)
    except Exception as e:
        results["graftlint_error"] = repr(e)
    print(json.dumps(results))
    sys.stdout.flush()
    sys.stderr.flush()
    ok = (
        "pipeline_error" not in results
        and "pipeline_stage_error" not in results
        and results.get("stage_amortized_block_ms") is not None
        and results.get("prefetch_overlap_pct") is not None
        # the resize fast path's regression gate: the second resize of
        # the run must find its executable in the compile cache
        and "resize_error" not in results
        and (results.get("compile_cache_hit_pct") or 0) > 0
        and results.get("resize_second_cache_hit") is True
        # the compressed-collective gates: int8 + error feedback must
        # track the fp32 baseline and actually shrink wire traffic,
        # or the compression path has silently rotted
        and "grad_sync_error" not in results
        and results.get("grad_sync_ms") is not None
        and results.get("comm_overlap_pct") is not None
        # explicit None checks: a gap of exactly 0.0 is a PASS (falsy
        # `or`-defaulting would flip perfect parity into a failure)
        and results.get("grad_sync_loss_gap") is not None
        and results["grad_sync_loss_gap"] <= GRAD_SYNC_LOSS_GATE
        and results.get("grad_sync_wire_ratio") is not None
        and results["grad_sync_wire_ratio"] <= GRAD_SYNC_WIRE_GATE
        # the topology gates: the probed LinkModel must be sane
        # (ici >= dcn >= host) and warm-cached per fingerprint, the
        # two-level schedule must move strictly fewer cross-slice
        # bytes than the flat ring at fp32 bit parity, and the
        # dry-runner's comm term must be priced from the installed
        # model, not the legacy flat-ICI constant
        and "topology_error" not in results
        and results.get("link_ordering_ok") is True
        and results.get("topology_probe_cache_hit") is True
        and results.get("grad_sync_2level_wire_vs_flat") is not None
        and results["grad_sync_2level_wire_vs_flat"] < 1.0
        and results.get("grad_sync_2level_parity") is True
        and results.get("dry_run_priced_from_link_model") is True
        # the sparse-sync gates (ISSUE 18): the EF-composed top-k DCN
        # shard must halve the int8 shard's cross-slice bytes while
        # error feedback keeps the loss inside the int8 gate, density
        # 1.0 must be BITWISE with plain int8 (the sparse branch
        # cannot drift from the path it generalizes), and one striped
        # transfer must fold realized rail GB/s into the persisted
        # observed-rate snapshot that reprices get_link_model() after
        # a full in-process reset
        and "sparse_sync_error" not in results
        and results.get("grad_sync_dcn_wire_vs_int8") is not None
        and (
            results["grad_sync_dcn_wire_vs_int8"]
            <= SPARSE_SYNC_DCN_WIRE_GATE
        )
        and results.get("sparse_sync_loss_gap") is not None
        and results["sparse_sync_loss_gap"] <= GRAD_SYNC_LOSS_GATE
        and results.get("sparse_sync_density1_bitwise") is True
        and results.get("topology_observed_rates_persisted") == 1
        and results.get("topology_observed_pricing") is True
        # the hybrid-mesh gates (ISSUE 8): the explicit path must
        # engage on dp x fsdp and dp x tp meshes (no silent GSPMD
        # fallback), fsdp fp32 must be BITWISE with GSPMD and its
        # ZeRO schedule must move strictly fewer ring bytes than the
        # monolithic all-reduce, int8+EF on the dp axis must track
        # the baseline, and a dp x tp mesh must resize warm through
        # the AOT cache
        and "hybrid_sync_error" not in results
        and results.get("hybrid_sync_path_fsdp") == "explicit"
        and results.get("hybrid_sync_path_tp") == "explicit"
        and results.get("hybrid_sync_path_trainer") == "explicit"
        and results.get("hybrid_sync_no_fallback_log") is True
        and results.get("hybrid_sync_parity_fsdp") is True
        and results.get("hybrid_sync_parity_tp") is True
        and results.get("hybrid_sync_fsdp_wire_bytes") is not None
        and (
            results["hybrid_sync_fsdp_wire_bytes"]
            < results["hybrid_sync_gspmd_wire_bytes"]
        )
        and results.get("hybrid_sync_int8_loss_gap") is not None
        and results["hybrid_sync_int8_loss_gap"] <= GRAD_SYNC_LOSS_GATE
        and results.get("resize_downtime_warm_tp_ms") is not None
        and results.get("hybrid_resize_cache_hit") is True
        # the telemetry gates: the dumped trace must be valid Chrome-
        # trace JSON whose step spans are explained by their phase
        # children, and tracing must stay cheap enough to leave on
        and "trace_error" not in results
        and results.get("trace_valid") is True
        and results.get("trace_step_coverage_pct") is not None
        and results["trace_step_coverage_pct"] >= TRACE_COVERAGE_GATE_PCT
        and results.get("trace_overhead_ok") is True
        # the audit gates: an injected data-starvation delay must be
        # attributed to data_wait (not a neighbor component) within the
        # step gate, auditing must cost less than the tracer overhead
        # budget, and a pure price-drift scenario must be repriced by
        # the per-component calib without ever raising a regression
        # alarm — misattribution sends an SRE to the wrong subsystem
        and "audit_error" not in results
        and results.get("audit_alarm_component") == "data_wait"
        and results.get("audit_alarm_steps") is not None
        and results["audit_alarm_steps"] <= AUDIT_ATTRIBUTION_STEP_GATE
        and results.get("audit_neighbor_quiet") is True
        and results.get("audit_flight_evidence") is True
        and results.get("audit_overhead_ok") is True
        and results.get("audit_drift_no_alarm") is True
        and results.get("audit_drift_repriced_ok") is True
        # the durability gates: an injected torn write must be detected
        # and rolled back to the previous verified step, and persistent
        # ENOSPC must enter (and a healthy persist exit) shm-only
        # degraded mode — undetected corruption or a failed rollback is
        # a data-loss bug and must fail CI loudly
        and "recovery_error" not in results
        and results.get("recovery_torn_detected") is True
        and results.get("recovery_rollback_ok") is True
        and results.get("recovery_enospc_degraded") is True
        and results.get("recovery_enospc_recovered") is True
        and results.get("ckpt_recover_ms") is not None
        and (results.get("faults_triggered") or 0) > 0
        # the forensics gates: the goodput ledger's categories must sum
        # back to wall time (a broken partition corrupts the number the
        # Brain plans against), spans must actually flow into it, and
        # an injected crash must leave a flight-recorder bundle whose
        # trace loads as valid Chrome JSON — a black box that fails at
        # the crash is decoration
        and "forensics_error" not in results
        and results.get("goodput_closure_error_pct") is not None
        and (
            results["goodput_closure_error_pct"]
            <= results["goodput_closure_gate_pct"]
        )
        and (results.get("goodput_ledger_pct") or 0) > 0
        and results.get("flight_crash_injected") is True
        and results.get("flight_bundle_ok") is True
        and results.get("flight_trace_valid") is True
        # the brain cluster-scheduler gates (ISSUE 10): the closed
        # telemetry->decision->execution loop must converge to a
        # better aggregate goodput than the best static equal split,
        # report its decision->resized latency, and leave every
        # emitted plan slice acked-or-expired — a plan silently
        # dropped is invisible exactly when the loop is broken
        and "brain_error" not in results
        and results.get("brain_agg_goodput_closed") is not None
        and (
            results["brain_agg_goodput_closed"]
            > results["brain_agg_goodput_equal_split"]
        )
        and results.get("brain_decision_to_resized_ms") is not None
        and results.get("brain_plans_unresolved") == 0
        and (results.get("brain_plans_acked") or 0) > 0
        and (results.get("brain_plans_expired") or 0) > 0
        and (results.get("brain_outcome_rows") or 0) > 0
        # the chaos gates (ISSUE 11): an eviction mid-save must end in
        # a verified resumable checkpoint with BITWISE loss continuity,
        # the drain booked to the `eviction` goodput category and zero
        # wedged processes; a hard kill mid-step must lose at most one
        # commit interval of steps — survival regressing is exactly
        # what must fail CI loudly
        and "chaos_error" not in results
        and results.get("chaos_evict_ok") is True
        and results.get("chaos_evict_loss_bitwise") is True
        and (results.get("chaos_evict_goodput_eviction_s") or 0) > 0
        and results.get("chaos_evict_wedged_threads") == 0
        and results.get("chaos_kill_ok") is True
        and results.get("chaos_kill_lost_steps") is not None
        and (
            results["chaos_kill_lost_steps"]
            <= results["chaos_kill_commit_interval"]
        )
        # the SDC gates (ISSUE 20): the injected wrong-but-finite chip
        # must be detected within the step gate, convicted EXACTLY (no
        # innocent conviction across three seeds / three lanes),
        # rolled back to the verified step with bitwise loss
        # continuity on the clean remainder, quarantined out of the
        # next rendezvous world, and the always-on detector must cost
        # less than the tracer-overhead budget
        and "sdc_error" not in results
        and results.get("sdc_quarantine_ok") is True
        and results.get("sdc_detect_steps") is not None
        and results["sdc_detect_steps"] <= SDC_DETECT_STEP_GATE
        and results.get("sdc_convicted_exact") is True
        and results.get("sdc_innocent_convictions") == 0
        and results.get("sdc_rollback_ok") is True
        and results.get("sdc_loss_bitwise") is True
        and results.get("sdc_excluded_from_world") is True
        and results.get("sdc_overhead_ok") is True
        # the sparse-embedding gates (ISSUE 12): the overlapped
        # device-tier cycle must be strictly faster than the
        # synchronous host gather/scatter cycle (documented floor
        # SPARSE_OVERLAP_GATE), the HBM hot tier must absorb the
        # zipfian trace, warm embedding reshard must beat the full
        # npz re-import it replaces, and a chunked-delta restore must
        # be BITWISE loss-continuous with the uninterrupted run
        and "sparse_error" not in results
        and results.get("sparse_step_overlap_on_vs_off") is not None
        and (
            results["sparse_step_overlap_on_vs_off"]
            < SPARSE_OVERLAP_GATE
        )
        and results.get("embedding_gather_hit_pct") is not None
        and (
            results["embedding_gather_hit_pct"] >= SPARSE_HIT_GATE_PCT
        )
        and results.get("embedding_reshard_warm_ms") is not None
        and (
            results["embedding_reshard_warm_ms"]
            < results["embedding_reshard_full_ms"]
        )
        and results.get("sparse_resume_bitwise") is True
        # the mesh-matrix gates (ISSUE 13): every axis combination the
        # strategy search emits must take the explicit sync path — pp
        # within the parity gate with its comm_exposed priced strictly
        # below the post-drain monolithic fallback, ep parity + the
        # capacity rebalance cutting overflow drops on skewed routing,
        # 3D parity with tp adding no dp-leg bytes, and the 6-of-8
        # micro-batch rebalance beating the idle-ranks alternative on
        # the per-rank critical path with zero idle ranks
        and "mesh_matrix_error" not in results
        and results.get("mesh_matrix_pp_path") == "explicit"
        and results.get("mesh_matrix_ep_path") == "explicit"
        and results.get("mesh_matrix_3d_path") == "explicit"
        and results.get("mesh_matrix_pp_parity") is True
        and results.get("mesh_matrix_pp_comm_exposed_ratio") is not None
        and results["mesh_matrix_pp_comm_exposed_ratio"] < 1.0
        and results.get("mesh_matrix_ep_parity") is True
        and results.get("mesh_matrix_ep_drop_rebalanced") is not None
        and (
            results["mesh_matrix_ep_drop_rebalanced"]
            < results["mesh_matrix_ep_drop_static"]
        )
        and results.get("mesh_matrix_3d_parity") is True
        and results.get("mesh_matrix_3d_wire_vs_fsdp") is not None
        and results["mesh_matrix_3d_wire_vs_fsdp"] <= 1.0
        and (results.get("mesh_matrix_mb_pad") or 0) > 0
        and results.get("mesh_matrix_mb_idle_ranks") == 0
        and results.get("mesh_matrix_mb_throughput_gain") is not None
        and results["mesh_matrix_mb_throughput_gain"] > 1.0
        # warm pp resize recorded (reshard + AOT-cache hit)
        and "resize_pp_error" not in results
        and results.get("resize_downtime_warm_pp_ms") is not None
        # the control-plane gates (ISSUE 14): 1k fake workers against a
        # real gRPC master must hold steady state at ~1 RPC/node/tick,
        # delta telemetry must stay under 0.4x the full-payload bytes
        # WITH identical reconstructed master-side scalars, a master
        # restart must converge through resync, the multi-path arbiter
        # must expose strictly less step-blocked transfer time than
        # serialized, and the dry-runner's host-leg pricing must be live
        and "control_plane_error" not in results
        and results.get("control_plane_rpcs_per_node_tick") is not None
        and (
            results["control_plane_rpcs_per_node_tick"]
            <= CONTROL_PLANE_RPC_GATE
        )
        and results.get("control_plane_rpc_p99_ms") is not None
        and (
            results["control_plane_rpc_p99_ms"]
            <= CONTROL_PLANE_P99_GATE_MS
        )
        and results.get("control_plane_delta_vs_full_bytes") is not None
        and (
            results["control_plane_delta_vs_full_bytes"]
            <= CONTROL_PLANE_DELTA_GATE
        )
        and results.get("control_plane_reconstructed_ok") is True
        and results.get("control_plane_resync_converged") is True
        and results.get("transfer_blocked_ms_arbitrated") is not None
        and (
            results["transfer_blocked_ms_arbitrated"]
            < results["transfer_blocked_ms_serialized"]
        )
        and results.get("control_plane_host_priced") is True
        # the multi-rail gates (ISSUE 16): striping across the emulated
        # two-rail link must beat the best single rail by the
        # documented floor, striped payloads must land bitwise with the
        # combined crc matching the one-pass digest, the hidden-
        # fraction calibration must warm-hit its fingerprint cache, and
        # pricing must consume the measured fraction once it exists
        and "multirail_error" not in results
        and results.get("multirail_effective_GBps_vs_single") is not None
        and (
            results["multirail_effective_GBps_vs_single"]
            >= MULTIRAIL_SPEEDUP_GATE
        )
        and results.get("stripe_crc_parity") == "bitwise"
        and results.get("arbiter_calibration_cache_hit") is True
        and results.get("multirail_priced_from_measured") is True
        # the serve-while-training gates (ISSUE 17): the subscriber
        # must map frames zero-copy and serve tokens bitwise-identical
        # to a direct crc-gated restore, the provoked commit-mid-read
        # race must be caught by the seqlock generation re-check, and
        # co-located serving must pay ≤10% training goodput while
        # earning tokens — with gap-confined serving moving the fleet
        # goodput number by at most ±1 point and its soak seconds
        # visible in the ledger
        and "serving_error" not in results
        and results.get("serving_zero_copy") is True
        and results.get("serving_bitwise_vs_restore") is True
        and results.get("serving_torn_provoked") is True
        and results.get("serving_torn_caught") is True
        and results.get("serving_tokens_per_s") is not None
        and results["serving_tokens_per_s"] > 0
        and (results.get("serving_soak_s") or 0) > 0
        and results.get("serving_goodput_loss_pct") is not None
        and (
            results["serving_goodput_loss_pct"]
            <= SERVING_GOODPUT_LOSS_GATE_PCT
        )
        and results.get("serving_gap_confined_goodput_delta_pct")
        is not None
        and (
            abs(results["serving_gap_confined_goodput_delta_pct"])
            <= SERVING_GAP_DELTA_GATE_PCT
        )
        # the static-analysis gate (ISSUE 15): the tree must be
        # graftlint-clean — an unsuppressed invariant violation
        # (lock discipline, span leak, RPC matrix hole, metric/doc
        # drift, dead fault site, unfsynced rename) fails CI like a
        # perf regression does
        and "graftlint_error" not in results
        and results.get("graftlint_clean") is True
    )
    return 0 if ok else 1


def run_mfu(jax, results: dict):
    """Compute-bound probe: GPT-2 124M, bf16, on-device data, chained
    state. No checkpointing, no host transfers inside the timed region.

    Timing forces the dependency chain by materializing the LAST step's
    loss (which depends on every prior step's params), so it covers
    execution and not only dispatch.
    """
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import (
        build_train_step,
        gpt2_small,
        init_sharded_state,
    )
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    on_accel = jax.devices()[0].platform != "cpu"
    if not on_accel:
        results["mfu_pct"] = None
        return
    # bs32/seq512 measured best on v5e (44.6% vs 27% at bs8/seq1024):
    # enough tokens to fill the MXU without remat or HBM pressure
    batch, seq = 32, 512
    cfg = replace(gpt2_small(), max_seq_len=seq)
    mesh = build_mesh(MeshConfig(dp=len(jax.devices())))
    tx = optax.adamw(3e-4)
    state, _ = init_sharded_state(jax.random.PRNGKey(1), cfg, mesh, tx)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state.params)
    )
    step_fn = build_train_step(cfg, mesh, tx, donate=True)

    # the measured region is a lax.scan of real train steps with a
    # FRESH on-device batch each step (fold_in per step — same
    # synthetic-corpus data as before, no host in the loop), so host
    # dispatch between steps stays out of the per-step number
    import functools

    from jax import lax

    # 200 iters: a short scan smears the fixed dispatch+readback cost
    # of one run_steps call into the per-step number
    iters = 200

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
    def run_steps(state, key, n):
        def body(st, i):
            x = jax.random.randint(
                jax.random.fold_in(key, i),
                (batch, seq),
                0,
                cfg.vocab_size,
                jnp.int32,
            )
            st, m = step_fn(st, x, x)
            return st, m["loss"]

        return lax.scan(body, state, jnp.arange(n))

    state, losses = run_steps(state, jax.random.PRNGKey(0), iters)
    float(losses[-1])  # compile + warmup
    t0 = time.perf_counter()
    state, losses = run_steps(state, jax.random.PRNGKey(1), iters)
    float(losses[-1])  # forces the whole chain
    dt = (time.perf_counter() - t0) / iters

    flops = _model_flops_per_step(cfg, batch, seq, n_params)
    tflops = flops / dt / 1e12
    peak = _chip_peak_tflops(jax.devices()[0])
    results["mfu_small_tflops"] = round(tflops, 1)
    results["mfu_small_pct"] = (
        round(100.0 * tflops / (peak * len(jax.devices())), 1)
        if peak
        else None
    )
    results["mfu_small_step_s"] = round(dt, 4)
    results["mfu_small_model"] = f"gpt2_small(124M) bs{batch} seq{seq} bf16"
    results["device_kind"] = getattr(
        jax.devices()[0], "device_kind", "unknown"
    )


# every leg of a full run, in order: (name of its ``<name>_error`` key, leg)
_LEGS = (
    ("staging", run_staging_bench),
    ("sp_compare", run_sp_compare),
    ("coworker_feed", lambda jax, results: run_coworker_feed(results)),
    ("pipeline", run_pipeline_bench),
    ("resize", run_resize_bench),
    ("grad_sync", run_grad_sync_bench),
    ("topology", run_topology_bench),
    ("sparse_sync", run_sparse_sync_bench),
    ("hybrid_sync", run_hybrid_sync_bench),
    ("trace", run_trace_bench),
    ("audit", run_audit_bench),
    ("recovery", run_recovery_bench),
    ("forensics", run_forensics_bench),
    ("brain", run_brain_bench),
    ("chaos", run_chaos_bench),
    ("sdc", run_sdc_bench),
    ("sparse", run_sparse_bench),
    ("control_plane", run_control_plane_bench),
    ("multirail", run_multirail_bench),
    ("serving", run_serving_bench),
    ("mfu_small", run_mfu),
    ("mfu_big", run_mfu_big),
)


def main() -> int:
    """A full run in ONE process, which owns the chip from start to end.
    A leg that raises is recorded under ``<name>_error`` and the run
    goes on; a run with any error key exits nonzero."""
    import jax

    results: dict = {}
    if not run_goodput(jax, results):
        print(json.dumps({"metric": "error", "value": -1}))
        return 1
    for name, leg in _LEGS:
        try:
            leg(jax, results)
        except Exception as e:
            results[f"{name}_error"] = repr(e)
    print(json.dumps(results))
    failed = sorted(k for k in results if k.endswith("_error"))
    if failed:
        print(f"bench: legs failed: {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(run_smoke())
    sys.exit(main())

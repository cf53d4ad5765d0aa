"""``build_optimizer``: the optimizer + LR schedule a train script hands
to ``ElasticTrainer`` (re-exported from ``trainer.py``)."""

from __future__ import annotations


def build_optimizer(
    name: str = "adamw",
    lr: float = 3e-4,
    schedule: str = "constant",
    warmup_steps: int = 0,
    total_steps: int = 10_000,
    weight_decay: float = 0.0,
    **kwargs,
):
    """Optimizer + LR schedule, retune-compatible (the AtorchTrainer
    ``lr_scheduler_type`` surface, ref atorch_trainer.py:127).

    The returned transform is built with ``optax.inject_hyperparams`` so
    two knobs stay live in ``opt_state.hyperparams``:

    - ``learning_rate`` — driven per-step by the chosen schedule
      ("constant" | "cosine" | "linear"; warmup_steps prepends a linear
      warmup);
    - ``retune_scale`` — the master's batch-size linear-scaling factor
      (ElasticTrainer._apply_lr_scale writes it), COMPOSED with the
      schedule instead of being overwritten by it.
    """
    import optax

    if schedule == "constant":
        lr_fn = (
            optax.linear_schedule(0.0, lr, warmup_steps)
            if warmup_steps
            else lr
        )
    elif schedule == "cosine":
        # warmup_steps=0 means NO warmup: start at peak (forcing a
        # 1-step warmup would make the first update a dead lr=0 step)
        lr_fn = (
            optax.warmup_cosine_decay_schedule(
                init_value=0.0,
                peak_value=lr,
                warmup_steps=warmup_steps,
                decay_steps=total_steps,
            )
            if warmup_steps
            else optax.cosine_decay_schedule(lr, total_steps)
        )
    elif schedule == "linear":
        decay = optax.linear_schedule(
            lr, 0.0, max(total_steps - warmup_steps, 1)
        )
        lr_fn = (
            optax.join_schedules(
                [optax.linear_schedule(0.0, lr, warmup_steps), decay],
                [warmup_steps],
            )
            if warmup_steps
            else decay
        )
    else:
        raise ValueError(f"unknown lr schedule {schedule!r}")

    if name not in ("adamw", "adam", "sgd", "agd", "adamw_8bit"):
        raise ValueError(f"unknown optimizer {name!r}")
    # ``use_pallas`` is retired: ``adamw_8bit`` reads its layout and its
    # step from the leaf and the backend. The benchmark's configuration
    # files still write ``"use_pallas": false``; this handling goes with
    # the ``benchmark`` PR that removes that line (ROADMAP.md, Design 7(b))
    if name == "adamw_8bit" and kwargs.pop("use_pallas", False):
        raise ValueError(
            "use_pallas is retired and selects nothing: adamw_8bit reads "
            "its layout and its step from the leaf and the backend"
        )

    def make(learning_rate, retune_scale):
        # weight_decay applies to EVERY optimizer: decoupled (after the
        # adaptive direction) for adamw/adam/agd/adamw_8bit, classic
        # L2-into-update for sgd. add_decayed_weights(0.0) is a no-op.
        if name == "adamw":
            opt = optax.adamw(
                learning_rate, weight_decay=weight_decay, **kwargs
            )
        elif name == "adam":
            opt = optax.chain(
                optax.scale_by_adam(**kwargs),
                optax.add_decayed_weights(weight_decay),
                optax.scale_by_learning_rate(learning_rate),
            )
        elif name == "agd":
            from dlrover_tpu.ops.optimizers import agd

            opt = agd(
                learning_rate, weight_decay=weight_decay, **kwargs
            )
        elif name == "adamw_8bit":
            from dlrover_tpu.ops.quantized_optim import adamw_8bit

            opt = adamw_8bit(
                learning_rate, weight_decay=weight_decay, **kwargs
            )
        else:
            opt = optax.chain(
                optax.add_decayed_weights(weight_decay),
                optax.sgd(learning_rate, **kwargs),
            )
        return optax.chain(opt, optax.scale(retune_scale))

    tx = optax.inject_hyperparams(make)(
        learning_rate=lr_fn, retune_scale=1.0
    )
    if name != "adamw_8bit":
        return tx
    from dlrover_tpu.ops.quantized_optim import (
        InPlaceTransformation,
        adamw_8bit,
    )

    def make_in_place(learning_rate, retune_scale):
        """``make``'s chain with ``update`` and ``optax.apply_updates`` as
        one entry: what comes back in the updates' place is the new
        parameters, ``retune_scale`` times the whole update inside the
        entry as ``optax.scale`` after it. The state is the chain's."""
        opt = adamw_8bit(learning_rate, weight_decay=weight_decay, **kwargs)

        def update_and_apply(grads, state, params):
            new_params, inner = opt.update_and_apply(
                grads, state[0], params, scale=retune_scale
            )
            return new_params, (inner, *state[1:])

        return optax.GradientTransformation(
            make(learning_rate, retune_scale).init, update_and_apply
        )

    # a second ``inject_hyperparams`` over the same state: both knobs are
    # read, and the schedule's count kept, by the code that ``update`` runs
    in_place = optax.inject_hyperparams(make_in_place)(
        learning_rate=lr_fn, retune_scale=1.0
    )
    return InPlaceTransformation(tx.init, tx.update, in_place.update)

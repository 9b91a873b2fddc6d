"""How a user builds the two paths, from a configuration file.

The few lines are copied from ``chip_smoke.build_train_step`` /
``build_serve_model`` and ``tools/chip_smoke_multichip.py`` rather than
imported: later PRs may change those files, not the yardstick.
"""
from __future__ import annotations

import numpy as np

# keys of a configuration file that are the model's sizes
ARCH_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
             "intermediate_size", "max_position_embeddings",
             "layer_norm_eps", "tie_word_embeddings", "use_bias")


def seed31(seed: int, stream: int = 0) -> int:
    """Any whole-number seed folded to 31 bits (jax's PRNGKey takes no
    more without x64), a different value per ``stream``."""
    return int(np.random.SeedSequence([int(seed), stream])
               .generate_state(1)[0] >> 1)


def build_model(pt, config: dict, seed: int, train: bool):
    """The program's own constructor, seeded: weights are drawn on the
    device in the dtype they are trained or served in."""
    models = pt.models
    arch = {k: config[k] for k in ARCH_KEYS if k in config}
    arch.update(config.get("model_kwargs", {}))
    cfg = getattr(models, config["config_class"])(**arch)
    pt.seed(seed31(seed, 1))
    pt.set_default_dtype(config["dtype"])
    try:
        model = getattr(models, config["model_class"])(cfg)
    finally:
        pt.set_default_dtype("float32")
    if not train:
        model.eval()
    return model


def build_train_step(pt, config: dict, model, devices):
    """AdamW as the configuration's ``trainer`` says, and ``TrainStep``
    over the configuration's mesh when it has one."""
    from paddle_tpu.jit import TrainStep

    tr = config["trainer"]
    opt = getattr(pt.optimizer, tr["optimizer"])(
        parameters=model.parameters(), **tr["optimizer_kwargs"])
    kw = {}
    mesh = config.get("mesh")
    if mesh:
        from paddle_tpu.distributed.auto_parallel.process_mesh import \
            ProcessMesh

        names = list(mesh["axes"])
        shape = [int(mesh["axes"][n]) for n in names]
        n = int(np.prod(shape))
        if len(devices) < n:
            raise RuntimeError("mesh %r needs %d devices, found %d"
                               % (mesh["axes"], n, len(devices)))
        kw["mesh"] = ProcessMesh(np.arange(n).reshape(shape),
                                 dim_names=names)
        kw["batch_specs"] = [tuple(s) for s in mesh["batch_specs"]]
    step = TrainStep(model, opt, grad_clip_norm=tr.get("grad_clip_norm"),
                     **kw)
    if mesh:
        # the model still holds the unsharded arrays it was built with,
        # all on the first chip; hand it the sharded ones before the
        # first step needs that memory
        step.sync_params_to_model()
    return step


def build_engine(pt, config: dict, model):
    return pt.serving.ServingEngine(model, **config["engine"])


def named_params(model) -> dict:
    """name -> the parameter's current device array (``Tensor.value``)."""
    return {n: p.value for n, p in model.named_parameters()}


def count_params(model) -> int:
    return sum(int(np.prod(p.shape)) for p in model.parameters())

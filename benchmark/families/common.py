"""The training recipe every family shares, the set-up pass that levels a
biased router's loads, and three small comparisons.

Not a family (``benchmark/families/__init__.py`` lists it under ``HELPERS``):
a family file knows its model's classes and key names and hands the MODEL
here; how a model becomes an engine through the program's entry points, and
how its step is lowered over abstract state for described chips, is one
recipe whatever the model (ROADMAP D16: it stood in four files). A family
imports this file and another family's PUBLIC members, never a private name.
"""

import numpy as np


def merged(config, section, rehearse):
    """``config[section]`` with the rehearsal's overrides laid over it."""
    def merge(a, b):
        out = dict(a)
        for k, v in b.items():
            out[k] = merge(a[k], v) if isinstance(v, dict) \
                and isinstance(a.get(k), dict) else v
        return out
    base = config[section]
    over = config["rehearse_cpu"].get(section, {}) if rehearse else {}
    return merge(base, over)


def engine_config(config, global_batch, seed, rehearse):
    return dict(merged(config, "train", rehearse)["engine"],
                train_batch_size=global_batch, seed=seed)


def build_train(model, config, global_batch, seed, devices, rehearse,
                example_len):
    """(engine, initial parameters) of ``model``. The weights are born
    sharded in one jitted call (``zero.Init``'s functional form; the example
    input is ``[global_batch, example_len]`` ids) and handed to
    ``dstpu.initialize`` as ``model_parameters``; the engine adopts those
    very buffers, so the caller's handle is valid until the first step
    donates them — long enough for the reference to read them."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh
    from deepspeed_tpu.runtime.zero.init import sharded_init

    ds = engine_config(config, global_batch, seed, rehearse)
    mesh = make_mesh(MeshConfig(data=len(devices)), devices=devices)
    zero = ds["zero_optimization"]
    params, _ = sharded_init(
        model, jax.random.PRNGKey(seed),
        jnp.zeros((global_batch, example_len), jnp.int32), mesh,
        stage=zero["stage"],
        param_persistence_threshold=zero.get(
            "stage3_param_persistence_threshold", 100000))
    engine, _, _, _ = dstpu.initialize(config=ds, model=model, mesh=mesh,
                                       model_parameters=params)
    return engine, params


def lower_train_step(model, config, traffic, devices):
    """``model``'s train step at the cell's real size, lowered over abstract
    state laid out as the engine lays it out on ``devices`` (a plain reshape
    onto the data axis: described chips have no attached topology to line
    up)."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as dstpu
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.runtime import precision as prec
    from deepspeed_tpu.runtime.engine import TrainState

    SDS = jax.ShapeDtypeStruct
    batch = traffic["global_batch"]
    mesh = Mesh(np.asarray(devices).reshape((1, len(devices), 1, 1, 1)),
                mesh_lib.AXIS_ORDER)
    engine, _, _, _ = dstpu.initialize(
        config=engine_config(config, batch, 0, False), model=model, mesh=mesh)
    ids = SDS((batch, traffic["seq_len"]), jnp.int32)
    params = jax.eval_shape(lambda r, x: engine.module.init(r, x)["params"],
                            jax.random.PRNGKey(0), ids)
    state = TrainState(
        params=params, opt_state=jax.eval_shape(engine.optimizer.init, params),
        scaler=jax.eval_shape(lambda: prec.init_scaler_state(engine.precision)),
        global_step=SDS((), jnp.int32), skipped_steps=SDS((), jnp.int32))
    engine.state_shardings = engine._build_state_shardings(state)
    engine._build_jit_fns()
    state = jax.tree_util.tree_map(
        lambda s, sh: SDS(s.shape, s.dtype, sharding=sh), state,
        engine.state_shardings)
    rng = jax.random.PRNGKey(0)
    return engine._jit_train_batch.lower(
        state,
        {"input_ids": SDS(ids.shape, ids.dtype,
                          sharding=mesh_lib.batch_sharding(mesh))},
        SDS(rng.shape, rng.dtype,
            sharding=NamedSharding(mesh, PartitionSpec())))


def balanced_selection_bias(model, params, module, names, how, global_batch,
                            vocab_size, seed):
    """(``params`` with the ``e_score_correction_bias`` of every expert
    layer ``params[name][module]``, ``name`` in ``names``, moved until the
    router's loads are level, {"rows_max_over_mean": the worst expert's rows
    over the mean, a layer, at the first and the last round, and the worst
    layer's at every round}). The bias exists to level the loads: the
    published recipe moves it during training by a rule outside the loss
    (the auxiliary-loss-free rule, arXiv 2408.15664: down where an expert is
    over the mean, up where under), and a checkpoint brings the values that
    rule left. A random router over a random stream is far from level (the
    worst of 128 experts takes 3-5.5 x the mean) and how many of the hot
    experts are among those held here is the seed's luck, so seeded weights
    with a DRAWN bias make the rows held, and with them the step's time, the
    seed's (a configuration's ``train.selection_bias_balance.why`` has the
    readings). So set-up runs the rule from the drawn bias on:
    ``how["rounds"]`` forward passes, each on a fresh batch of
    ``how["seq_len"]`` uniform token ids, every layer's bias moved after
    each by ``rate x clip(rows / mean - 1, -1, 1)``, the rate falling
    geometrically from ``rate_first`` to ``rate_last`` — a step proportional
    to the error where the published rule takes a fixed 1e-3 of its sign, so
    that dozens of rounds do what thousands of training steps do. One jitted
    scan; the bias stays a buffer held fixed over the window."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe.dropless import CHOICE_BIAS
    rates = jnp.asarray(np.geomspace(how["rate_first"], how["rate_last"],
                                     how["rounds"]), jnp.float32)
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1), how["rounds"])

    def with_biases(p, biases):
        return {**p, **{n: {**p[n], module: {**p[n][module], CHOICE_BIAS: b}}
                        for n, b in biases.items()}}

    @jax.jit
    def run(p):
        def one_round(biases, key_and_rate):
            key, rate = key_and_rate
            ids = jax.random.randint(key, (global_batch, how["seq_len"]), 0,
                                     vocab_size)
            _, seen = model.apply({"params": with_biases(p, biases)}, ids,
                                  mutable=["intermediates"])
            moved, worst = {}, []
            for n, bias in biases.items():
                top_e = seen["intermediates"][n][module]["top_e"][0]
                rows = jax.nn.one_hot(top_e, bias.shape[0],
                                      dtype=jnp.float32).sum(axis=(0, 1))
                over = rows / jnp.mean(rows) - 1.0
                moved[n] = bias - rate * jnp.clip(over, -1.0, 1.0)
                worst.append(jnp.max(over) + 1.0)
            return moved, jnp.stack(worst)

        return jax.lax.scan(
            one_round, {n: p[n][module][CHOICE_BIAS] for n in names},
            (keys, rates))

    biases, worst = run(params)
    biases = {n: jax.device_put(b, params[n][module][CHOICE_BIAS].sharding)
              for n, b in biases.items()}
    worst = np.asarray(worst)
    return with_biases(params, biases), {"rows_max_over_mean": {
        "first_round": worst[0].tolist(), "last_round": worst[-1].tolist(),
        "worst_layer_by_round": worst.max(axis=1).tolist()}}


# ------------------------------------- what the expert families compare with

def at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def rel(a, b):
    import jax.numpy as jnp
    a, b = (t.astype(jnp.float32) for t in (a, b))
    return jnp.linalg.norm(a - b) / jnp.linalg.norm(b)


def routing_differs(got, want):
    """Assignments of ``want`` [T, k] that ``got`` [T, k] did not choose."""
    import jax.numpy as jnp
    return jnp.sum(jnp.all(want[:, :, None] != got[:, None, :], axis=2))

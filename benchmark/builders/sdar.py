"""Builder of the ``sdar`` family: a configuration file of HF ``sdar_moe``
``config.json`` keys (cut as its ``reduced`` says) + the objective's
``block_length``, ``mask_token_id`` and ``noise`` ->
``SdarLMHeadModel.loss(ids, seed)`` under amp O2 (``keep_fp32_filter`` of
the model) + ``FusedAdam`` (AdamW) + ``build_train_step(donate=True,
has_aux=True)``, fed by the program's own ``CausalLMBatchLoader``. The
loader's rows stay the corpus's: the step's noise is drawn on the device,
inside the loss, from the batch's ``seed``.

Every layer is of one kind, so the leaf map is ``common.LeafMap``. It
assembles the trainer itself, as ``builders/lfm2.py`` does and for its
reason: ``common.assemble`` passes neither ``keep_fp32_filter`` nor
``has_aux`` on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

REFERENCE = "sdar"

_TOP = {"embed": ("model", "embed_tokens"),
        "norm_f": ("model", "norm", "scale"),
        "lm_head": ("lm_head",)}
_PER_LAYER = {"ln1": ("input_layernorm", "scale"),
              "q": ("self_attn", "q_proj", "kernel"),
              "k": ("self_attn", "k_proj", "kernel"),
              "v": ("self_attn", "v_proj", "kernel"),
              "q_norm": ("self_attn", "q_norm"),
              "k_norm": ("self_attn", "k_norm"),
              "o": ("self_attn", "o_proj", "kernel"),
              "ln2": ("post_attention_layernorm", "scale"),
              "router": ("expert_ffn", "experts", "router"),
              "w_gate_up": ("expert_ffn", "experts", "w_gate_up"),
              "w_down": ("expert_ffn", "experts", "w_down")}


def leaf_map(n_layers: int) -> common.LeafMap:
    return common.LeafMap(_TOP, _PER_LAYER,
                          lambda i: ("model", f"layers_{i}"), n_layers)


def model_config(config: dict):
    """The program's ``SdarConfig`` of a configuration file."""
    from apex_tpu.models.sdar import LinearNoise, SdarConfig

    deployment = config["deployment"]
    noise = config["noise"]
    if (noise["schedule"], noise["t_drawn_per"]) != ("linear", "block"):
        raise ValueError("the program draws the linear schedule's t once a "
                         "block")
    return SdarConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        num_experts=deployment["num_experts_published"],
        experts_held=config["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        block_length=config["block_length"],
        mask_token_id=config["mask_token_id"],
        noise=LinearNoise(floor=noise["floor"]),
        dtype=jnp.bfloat16, fused_kernels=True)


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    import apex_tpu.amp as amp
    from apex_tpu.data import CausalLMBatchLoader
    from apex_tpu.models.sdar import SdarLMHeadModel, keep_fp32_filter
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.train import build_train_step

    if mesh is not None or ddp is not None:
        raise ValueError("the sdar builder builds one chip's share")
    model = SdarLMHeadModel(model_config(config))
    leaves = leaf_map(config["num_hidden_layers"])
    opt = dict(config["optimizer"])
    if opt.pop("name") != "adamw":
        raise ValueError("the sdar builder trains with FusedAdam (AdamW)")
    optimizer = FusedAdam(lr=opt["lr"], weight_decay=opt["wd"],
                          betas=(opt["b1"], opt["b2"]), eps=opt["eps"],
                          adam_w_mode=True)

    def loss_fn(params, mb):
        return model.apply({"params": params}, mb["ids"], mb["seed"][0],
                           method="loss")

    def init_weights(key):
        return reference.init_weights(config, key)

    made = {}

    def make_state(key):
        params = leaves.to_program(init_weights(key))
        ids = jnp.zeros((1, 2 * config["block_length"]), jnp.int32)
        common.check_same_structure(params, jax.eval_shape(
            lambda k: model.init(k, ids, 0)["params"], jax.random.PRNGKey(0)))
        params, fused, handle = amp.initialize(
            params, optimizer, opt_level="O2", verbosity=0,
            keep_fp32_filter=keep_fp32_filter)
        made["step"] = build_train_step(
            loss_fn, fused, amp=handle, accum_steps=1, donate=True,
            has_aux=True)
        return made["step"].init(params)

    if abstract_on is not None:
        new_state = place = None
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=abstract_on),
            jax.eval_shape(make_state, key))
    else:
        new_state = jax.jit(make_state)
        state = new_state(key)
        place = lambda hb: jax.tree.map(jnp.asarray, hb)  # noqa: E731

    def sq_norms(tree):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree)

    moment_norms = jax.jit(lambda opt_state: sq_norms(opt_state.exp_avg))

    def grad_norms(state):
        m1 = moment_norms(state.opt_state)

        def finish(metrics):
            return leaves.to_reference(jax.tree.map(
                lambda x: float(x) / (1.0 - opt["b1"]), jax.device_get(m1)))

        return finish

    @jax.jit
    def change_norms(master, key):
        start = leaves.to_program(init_weights(key))
        return sq_norms(jax.tree.map(lambda a, b: a - b, master, start))

    def seeds(tb):
        return np.asarray(tb["seed"], np.int32).reshape(1)

    return common.Built(
        step=made["step"], state=state, place=place,
        program_batch=lambda tb: {"ids": tb["ids"][None],
                                  "seed": seeds(tb)[None]},
        reference_batch=lambda tb: {"ids": tb["ids"][None],
                                    "seed": seeds(tb)},
        feed=lambda corpus, rows, loader_seed, prefetch: CausalLMBatchLoader(
            corpus, batch_size=rows, seed=loader_seed, prefetch=prefetch),
        grad_norms=grad_norms,
        change_norms=lambda st, k: change_norms(st.opt_state.master, k),
        new_state=new_state,
        to_reference=lambda tree: leaves.to_reference(
            jax.device_get(tree)),
        optimizer=dict(config["optimizer"]),
        n_params=sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(state.params)))

"""The LM cells' weights, drawn by the benchmark in the published layout.

Every tensor has the name and the layout it has in the Hugging Face
``granitemoehybrid`` checkpoint: linear weights [out, in], the fused Mamba2
``in_proj`` [z | x B C | dt], the depthwise ``conv1d.weight`` [C, 1, K], the
experts' fused ``input_linear`` [E, gate | up, in] and ``output_linear``
[E, out, in].  Each tensor is drawn on the device from its own key, which
the run's seed and the tensor's name give (an expert's slice from the name
and the expert id), so that any layer can be drawn again without the rest:
``lm_reference`` reads the weights so, one layer at a time, and the program
gets them through ``to_program``, a converter into its stacked layout.  Only
the kept layers, the held experts and the vocabulary slice are drawn.

The initialisation is the configuration file's ``assumed`` (the published
config gives none): normal with std 1/sqrt(fan in) for every projection,
expert and router, std 0.02 for the embedding, norms 1, conv bias 0,
Mamba2's defaults for A (A = -exp(A_log), A ~ U[1, 16]) and dt
(softplus(dt_bias) log-uniform in [1e-3, 0.1], floor 1e-4), D = 1.
Matrices and norms are bfloat16, as the configuration holds them; A_log,
dt_bias and D are float32, as the checkpoint holds them.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

import system

EMBED, FINAL_NORM = "model.embed_tokens.weight", "model.norm.weight"
#: the program's stacks, by layer kind
STACK = {"mamba": "mamba_layers", "attention": "attn_layers"}


def _dims(c: dict) -> dict:
    d = int(c["hidden_size"])
    d_inner = int(c["mamba_expand"]) * d
    n, heads = int(c["mamba_d_state"]), int(c["mamba_n_heads"])
    return dict(d=d, d_inner=d_inner, n=n, heads=heads, conv=d_inner + 2 * n,
                k=int(c["mamba_d_conv"]), q=int(c["num_attention_heads"]),
                kv=int(c["num_key_value_heads"]),
                hd=d // int(c["num_attention_heads"]),
                f=int(c["intermediate_size"]), fs=int(c["shared_intermediate_size"]),
                e_all=int(c["published"]["num_local_experts"]))


def kinds(c: dict) -> list:
    """The kept layers' kinds, in the published order."""
    return list(c["layer_types"][: int(c["num_hidden_layers"])])


def held(c: dict) -> range:
    lo = int(c["expert_offset"])
    return range(lo, lo + int(c["num_local_experts"]))


def layer_tensors(c: dict, i: int) -> dict:
    """Layer ``i``'s tensors: name (after ``model.layers.{i}.``) ->
    (shape, init, std).  Expert tensors have the held experts' shape."""
    m = _dims(c)
    d, e = m["d"], len(held(c))
    t = {"input_layernorm.weight": ((d,), "ones", None),
         "post_attention_layernorm.weight": ((d,), "ones", None)}
    if kinds(c)[i] == "mamba":
        t.update({
            "mamba.in_proj.weight": ((2 * m["d_inner"] + 2 * m["n"] + m["heads"], d),
                                     "normal", d),
            "mamba.conv1d.weight": ((m["conv"], 1, m["k"]), "normal", m["k"]),
            "mamba.conv1d.bias": ((m["conv"],), "zeros", None),
            "mamba.dt_bias": ((m["heads"],), "dt_bias", None),
            "mamba.A_log": ((m["heads"],), "a_log", None),
            "mamba.D": ((m["heads"],), "ones32", None),
            "mamba.norm.weight": ((m["d_inner"],), "ones", None),
            "mamba.out_proj.weight": ((d, m["d_inner"]), "normal", m["d_inner"]),
        })
    else:
        t.update({
            "self_attn.q_proj.weight": ((m["q"] * m["hd"], d), "normal", d),
            "self_attn.k_proj.weight": ((m["kv"] * m["hd"], d), "normal", d),
            "self_attn.v_proj.weight": ((m["kv"] * m["hd"], d), "normal", d),
            "self_attn.o_proj.weight": ((d, m["q"] * m["hd"]), "normal", m["q"] * m["hd"]),
        })
    t.update({
        "block_sparse_moe.router.layer.weight": ((m["e_all"], d), "normal", d),
        "block_sparse_moe.input_linear.weight": ((e, 2 * m["f"], d), "expert", d),
        "block_sparse_moe.output_linear.weight": ((e, d, m["f"]), "expert", m["f"]),
        "shared_mlp.input_linear.weight": ((2 * m["fs"], d), "normal", d),
        "shared_mlp.output_linear.weight": ((d, m["fs"]), "normal", m["fs"]),
    })
    return t


def _key(seed: int, name: str):
    return jax.random.fold_in(system.device_key(seed, 11),
                              np.uint32(zlib.crc32(name.encode())))


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _draw(key, *, shape, kind, std):
    if kind == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if kind == "ones32":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus(dt_bias) = dt
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def draw_tensor(c: dict, seed: int, i: int, name: str):
    """One of layer ``i``'s tensors (not an expert's), by its name after
    ``model.layers.{i}.``."""
    shape, kind, fan_in = layer_tensors(c, i)[name]
    std = None if fan_in is None else float(1.0 / np.sqrt(fan_in))
    return _draw(_key(seed, f"model.layers.{i}.{name}"), shape=shape, kind=kind, std=std)


def draw_layer(c: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s tensors on the device, by their names after
    ``model.layers.{i}.``."""
    out = {}
    for name, (shape, kind, fan_in) in layer_tensors(c, i).items():
        if kind == "expert":
            full = f"model.layers.{i}.{name}"
            out[name] = jnp.stack([
                _draw(_key(seed, f"{full}[{e}]"), shape=shape[1:], kind="normal",
                      std=float(1.0 / np.sqrt(fan_in)))
                for e in held(c)])
        else:
            out[name] = draw_tensor(c, seed, i, name)
    return out


def draw_embed(c: dict, seed: int):
    """The embedding rows of the vocabulary slice [V, D] (tied: also the
    output projection)."""
    shape = (int(c["vocab_size"]), int(c["hidden_size"]))
    return _draw(_key(seed, EMBED), shape=shape, kind="normal", std=0.02)


def draw_final_norm(c: dict, seed: int):
    return _draw(_key(seed, FINAL_NORM), shape=(int(c["hidden_size"]),), kind="ones",
                 std=None)


def program_layer(c: dict, w: dict, kind: str) -> dict:
    """One layer's published tensors in the program's layout."""
    m = _dims(c)
    d, f, fs = m["d"], m["f"], m["fs"]
    if kind == "mamba":
        mixer = {"mamba": {
            "in_proj": w["mamba.in_proj.weight"].T,
            "conv_w": w["mamba.conv1d.weight"][:, 0, :].T,
            "conv_b": w["mamba.conv1d.bias"],
            "dt_bias": w["mamba.dt_bias"], "A_log": w["mamba.A_log"], "D": w["mamba.D"],
            "norm": w["mamba.norm.weight"],
            "out_proj": w["mamba.out_proj.weight"].T}}
    else:
        def heads(a, n):
            return a.T.reshape(d, n, m["hd"])

        mixer = {"attn": {
            "wq": heads(w["self_attn.q_proj.weight"], m["q"]),
            "wk": heads(w["self_attn.k_proj.weight"], m["kv"]),
            "wv": heads(w["self_attn.v_proj.weight"], m["kv"]),
            "wo": w["self_attn.o_proj.weight"].reshape(d, m["q"], m["hd"]).transpose(1, 2, 0)}}
    gu = w["block_sparse_moe.input_linear.weight"]
    sgu = w["shared_mlp.input_linear.weight"]
    moe = {"router": w["block_sparse_moe.router.layer.weight"].T,
           "w_gate": gu[:, :f].transpose(0, 2, 1), "w_up": gu[:, f:].transpose(0, 2, 1),
           "w_down": w["block_sparse_moe.output_linear.weight"].transpose(0, 2, 1),
           "shared": {"w_gate": sgu[:fs].T, "w_up": sgu[fs:].T,
                      "w_down": w["shared_mlp.output_linear.weight"].T}}
    return {"ln_mix": w["input_layernorm.weight"], **mixer,
            "ln_ffn": w["post_attention_layernorm.weight"], "moe": moe}


def to_program(c: dict, seed: int, like: dict) -> dict:
    """The program's weights: the published tensors drawn layer by layer and
    written into the program's stacks (``like``: its tree of shapes and
    dtypes), so that one layer's draw is the most held beside them."""
    put = jax.jit(lambda stack, leaf, i: jax.lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    out = {"embed": draw_embed(c, seed).astype(like["embed"].dtype),
           "ln_f": draw_final_norm(c, seed).astype(like["ln_f"].dtype)}
    for kind, stack in STACK.items():
        if stack in like:
            out[stack] = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), like[stack])
    seen = dict.fromkeys(STACK, 0)
    for i, kind in enumerate(kinds(c)):
        one = program_layer(c, draw_layer(c, seed, i), kind)
        stack = STACK[kind]
        shapes = jax.tree.map(lambda a: a.shape[1:], out[stack])
        got = jax.tree.map(lambda a: a.shape, one)
        if got != shapes:
            raise ValueError(f"layer {i}: converted shapes {got} != program's {shapes}")
        out[stack] = jax.tree.map(lambda s, a: put(s, a, np.int32(seen[kind])),
                                  out[stack], one)
        seen[kind] += 1
    return jax.block_until_ready(out)

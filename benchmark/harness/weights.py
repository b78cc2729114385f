"""GPT weights from the seed: one jitted call, on the device, in the type
the cell trains them in.  Names are framework-neutral; `to_program`
maps them onto `paddle_tpu.models.gpt` parameter names."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(cfg):
    """name -> (shape, kind); kind is 'matrix', 'out' (residual-scaled),
    'norm' (about one) or 'bias'."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"wte": ((cfg["vocab_size"], h), "matrix"),
           "wpe": ((cfg["max_position_embeddings"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"h.{i}."
        out.update({
            pre + "ln_1.w": ((h,), "norm"), pre + "ln_1.b": ((h,), "bias"),
            pre + "qkv.w": ((h, 3 * h), "matrix"), pre + "qkv.b": ((3 * h,), "bias"),
            pre + "out.w": ((h, h), "out"), pre + "out.b": ((h,), "bias"),
            pre + "ln_2.w": ((h,), "norm"), pre + "ln_2.b": ((h,), "bias"),
            pre + "up.w": ((h, f), "matrix"), pre + "up.b": ((f,), "bias"),
            pre + "down.w": ((f, h), "out"), pre + "down.b": ((h,), "bias"),
        })
    out.update({"ln_f.w": ((h,), "norm"), "ln_f.b": ((h,), "bias")})
    return out


def _generate(cfg, key, dtype):
    std = cfg["initializer_range"]
    out_std = std / (2.0 * cfg["num_hidden_layers"]) ** 0.5
    tree = {}
    for i, (name, (shape, kind)) in enumerate(shapes(cfg).items()):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + std * x
        else:
            x = (out_std if kind == "out" else std) * x
        # rounded to the type asked for, handed on as float32 holding that value
        tree[name] = x.astype(dtype).astype(jnp.float32)
    return tree


def key_of(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make(cfg, seed, dtype="float32"):
    return jax.jit(lambda k: _generate(cfg, k, jnp.dtype(dtype)))(key_of(seed))


_PROGRAM = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
            "ln_f.w": "gpt.ln_f.weight", "ln_f.b": "gpt.ln_f.bias"}
_PART = {"ln_1.w": "ln_1.weight", "ln_1.b": "ln_1.bias",
         "qkv.w": "attn.qkv_proj.weight", "qkv.b": "attn.qkv_proj.bias",
         "out.w": "attn.out_proj.weight", "out.b": "attn.out_proj.bias",
         "ln_2.w": "ln_2.weight", "ln_2.b": "ln_2.bias",
         "up.w": "mlp.up_proj.weight", "up.b": "mlp.up_proj.bias",
         "down.w": "mlp.down_proj.weight", "down.b": "mlp.down_proj.bias"}


def program_name(name):
    if name in _PROGRAM:
        return _PROGRAM[name]
    _, i, part = name.split(".", 2)
    return f"gpt.h.{i}.{_PART[part]}"


def load_into(model, tree):
    """Put the benchmark's weights into a `paddle_tpu.models.gpt` model,
    leaf for leaf; any leaf without a partner is an error."""
    w = {program_name(n): v for n, v in tree.items()}
    for name, p in model.named_parameters():
        if name not in w or p._value.shape != w[name].shape:
            raise RuntimeError(f"weights: no leaf of shape {p._value.shape} for {name}")
        p._value = w.pop(name)
    if w:
        raise RuntimeError(f"weights: the model lacks {sorted(w)}")


def logical_leaves(tree):
    """The leaves the comparison counts: the fused QKV projection is split
    into its query, key and value parts (thirds of the output axis), because
    the key's bias has no gradient under softmax and would otherwise hide in
    a leaf whose other two thirds have one."""
    out = {}
    for n, x in tree.items():
        if n.endswith(("qkv.w", "qkv.b")):
            third = x.shape[-1] // 3
            for j, part in enumerate("qkv"):
                out[f"{n}.{part}"] = x[..., j * third:(j + 1) * third]
        else:
            out[n] = x
    return out

"""Sharding-aware safetensors checkpoint IO for the Llama family.

Role of the reference's vLLM weight loading
(/root/reference/python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:57-63 delegates to vLLM; vLLM reads HF safetensors into
torch), rebuilt TPU-native:

- a self-contained safetensors parser (the format is 8-byte little-endian
  header length + JSON header + raw tensor bytes) over ``np.memmap`` so
  slice reads touch only the bytes a shard needs — no torch, no
  full-tensor materialization;
- an HF-Llama name/layout mapping onto this repo's stacked-layer pytree
  (``models/llama.py`` ``init_params``: per-layer weights stacked on a
  leading ``layers`` axis, matmul weights stored input-major, i.e. the
  TRANSPOSE of HF's (out, in) torch linear layout);
- per-shard loading onto a ``jax.sharding.Mesh`` via
  ``jax.make_array_from_callback``: each device's addressable shard
  triggers one windowed read of exactly its slice (per-host shard reads
  on an fsdp×tp mesh — the multi-host case reads only the host's
  shards), cast to the target dtype shard-by-shard so host memory stays
  bounded at the largest single shard;
- a writer (HF layout, size-sharded files + ``model.safetensors.index
  .json``) so tests round-trip and trained params export back to the
  ecosystem format.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes

from . import llama
from ..parallel.sharding import named_sharding

_DTYPES: Dict[str, Any] = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "BF16": ml_dtypes.bfloat16, "I64": np.int64, "I32": np.int32,
    "I16": np.int16, "I8": np.int8, "U8": np.uint8, "BOOL": np.bool_,
    "U16": np.uint16, "U32": np.uint32, "U64": np.uint64,
}
_DTYPE_NAMES = {np.dtype(v): k for k, v in _DTYPES.items()}


class SafeTensorsFile:
    """Zero-copy reader: tensors are memory-mapped views; ``read`` with a
    numpy index touches only the pages the slice spans."""

    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as f:
            hlen = int.from_bytes(f.read(8), "little")
            header = json.loads(f.read(hlen).decode("utf-8"))
        self.metadata = header.pop("__metadata__", {})
        self.tensors: Dict[str, dict] = header
        self._base = 8 + hlen
        self._mm = np.memmap(path, np.uint8, mode="r")

    def keys(self) -> List[str]:
        return list(self.tensors)

    def info(self, name: str) -> Tuple[Tuple[int, ...], np.dtype]:
        ent = self.tensors[name]
        return tuple(ent["shape"]), np.dtype(_DTYPES[ent["dtype"]])

    def read(self, name: str, index: Any = None) -> np.ndarray:
        ent = self.tensors[name]
        dt = np.dtype(_DTYPES[ent["dtype"]])
        a, b = ent["data_offsets"]
        arr = self._mm[self._base + a:self._base + b].view(dt)
        arr = arr.reshape(tuple(ent["shape"]))
        return arr if index is None else arr[index]


def write_safetensors(path: str, tensors: Dict[str, np.ndarray],
                      metadata: Optional[Dict[str, str]] = None) -> None:
    header: Dict[str, Any] = {}
    if metadata:
        header["__metadata__"] = {k: str(v) for k, v in metadata.items()}
    arrays = []
    off = 0
    for name, t in tensors.items():
        t = np.ascontiguousarray(t)
        arrays.append(t)
        header[name] = {"dtype": _DTYPE_NAMES[t.dtype],
                        "shape": list(t.shape),
                        "data_offsets": [off, off + t.nbytes]}
        off += t.nbytes
    hjson = json.dumps(header).encode("utf-8")
    hjson += b" " * (-len(hjson) % 8)          # HF pads headers with spaces
    with open(path, "wb") as f:
        f.write(len(hjson).to_bytes(8, "little"))
        f.write(hjson)
        for t in arrays:
            f.write(t.tobytes())


class _FileSet:
    """Resolves tensor names across a single- or index-sharded checkpoint
    directory; files open lazily and stay cached (mmap is cheap)."""

    def __init__(self, ckpt_dir: str):
        self.dir = ckpt_dir
        self._open: Dict[str, SafeTensorsFile] = {}
        index = os.path.join(ckpt_dir, "model.safetensors.index.json")
        if os.path.exists(index):
            with open(index) as f:
                self.weight_map: Dict[str, str] = json.load(f)["weight_map"]
        else:
            single = os.path.join(ckpt_dir, "model.safetensors")
            if os.path.isfile(ckpt_dir):           # direct file path
                single, self.dir = ckpt_dir, os.path.dirname(ckpt_dir)
            st = SafeTensorsFile(single)
            self._open[os.path.basename(single)] = st
            self.weight_map = {k: os.path.basename(single)
                               for k in st.keys()}

    def __contains__(self, name: str) -> bool:
        return name in self.weight_map

    def file(self, name: str) -> SafeTensorsFile:
        fname = self.weight_map[name]
        if fname not in self._open:
            self._open[fname] = SafeTensorsFile(
                os.path.join(self.dir, fname))
        return self._open[fname]

    def read(self, name: str, index: Any = None) -> np.ndarray:
        return self.file(name).read(name, index)

    def info(self, name: str) -> Tuple[Tuple[int, ...], np.dtype]:
        return self.file(name).info(name)


# ------------------------------------------------------------------ HF naming

_L = "model.layers.{l}."


def _norm_index(index, shape) -> Tuple[slice, ...]:
    """make_array_from_callback hands a tuple of slices (possibly with
    None bounds); normalize to concrete per-dim slices."""
    if index is None:
        index = (slice(None),) * len(shape)
    out = []
    for dim, sl in zip(shape, index):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        out.append(slice(start, stop))
    return tuple(out)


class _Leaf:
    """One target-pytree leaf: target shape + a slice reader."""

    def __init__(self, shape: Tuple[int, ...],
                 read: Callable[[Tuple[slice, ...]], np.ndarray]):
        self.shape = shape
        self._read = read

    def read(self, index) -> np.ndarray:
        return self._read(_norm_index(index, self.shape))


def _direct(files: _FileSet, name: str, shape) -> _Leaf:
    return _Leaf(tuple(shape), lambda idx: files.read(name, idx))


def _transposed(files: _FileSet, name: str, shape) -> _Leaf:
    """Target = HF tensor transposed: read the swapped slice, then .T —
    only the requested window crosses the mmap."""
    def read(idx):
        r, c = idx
        return files.read(name, (c, r)).T
    return _Leaf(tuple(shape), read)


def _stacked(files: _FileSet, fmt: str, shape,
             transpose: bool) -> _Leaf:
    """Target (L, *rest) stacking per-layer HF tensors on a new leading
    axis; per-layer windows read independently so a layer-sharded (pp)
    load touches only its layers."""
    def read(idx):
        lsl, rest = idx[0], idx[1:]
        per = []
        for l in range(lsl.start, lsl.stop):
            name = fmt.format(l=l)
            if transpose:
                r, c = rest
                per.append(files.read(name, (c, r)).T)
            else:
                per.append(files.read(name, rest))
        return np.stack(per)
    return _Leaf(tuple(shape), read)


def _stacked_experts(files: _FileSet, fmt: str, shape) -> _Leaf:
    """Target (L, E, a, b) from per-layer-per-expert HF tensors stored
    (b, a) (Mixtral block_sparse_moe layout)."""
    def read(idx):
        lsl, esl, a, b = idx
        layers = []
        for l in range(lsl.start, lsl.stop):
            experts = [files.read(fmt.format(l=l, e=e), (b, a)).T
                       for e in range(esl.start, esl.stop)]
            layers.append(np.stack(experts))
        return np.stack(layers)
    return _Leaf(tuple(shape), read)


def _llama_leaf_specs(cfg: llama.LlamaConfig,
                      files: _FileSet) -> Dict[str, Any]:
    """Pytree of _Leaf readers mirroring init_params' structure."""
    h, L, v = cfg.hidden, cfg.n_layers, cfg.vocab_size
    if cfg.n_experts:
        E, F = cfg.n_experts, cfg.ffn
        mlp = {
            "router": _stacked(
                files, _L + "block_sparse_moe.gate.weight",
                (L, h, E), transpose=True),
            # Mixtral: w1=gate, w3=up(in), w2=down
            "wg": _stacked_experts(
                files, _L + "block_sparse_moe.experts.{e}.w1.weight",
                (L, E, h, F)),
            "wi": _stacked_experts(
                files, _L + "block_sparse_moe.experts.{e}.w3.weight",
                (L, E, h, F)),
            "wd": _stacked_experts(
                files, _L + "block_sparse_moe.experts.{e}.w2.weight",
                (L, E, F, h)),
        }
    else:
        mlp = {
            "wg": _stacked(files, _L + "mlp.gate_proj.weight",
                           (L, h, cfg.ffn), transpose=True),
            "wi": _stacked(files, _L + "mlp.up_proj.weight",
                           (L, h, cfg.ffn), transpose=True),
            "wd": _stacked(files, _L + "mlp.down_proj.weight",
                           (L, cfg.ffn, h), transpose=True),
        }
    if "lm_head.weight" in files:
        lm_head = _transposed(files, "lm_head.weight", (h, v))
    else:   # tied embeddings (Llama-3.2 1B/3B ship no lm_head tensor)
        lm_head = _transposed(files, "model.embed_tokens.weight", (h, v))
    return {
        "embed": _direct(files, "model.embed_tokens.weight", (v, h)),
        "layers": {
            "wq": _stacked(files, _L + "self_attn.q_proj.weight",
                           (L, h, cfg.q_dim), transpose=True),
            "wk": _stacked(files, _L + "self_attn.k_proj.weight",
                           (L, h, cfg.kv_dim), transpose=True),
            "wv": _stacked(files, _L + "self_attn.v_proj.weight",
                           (L, h, cfg.kv_dim), transpose=True),
            "wo": _stacked(files, _L + "self_attn.o_proj.weight",
                           (L, cfg.q_dim, h), transpose=True),
            **mlp,
            "ln1": _stacked(files, _L + "input_layernorm.weight",
                            (L, h), transpose=False),
            "ln2": _stacked(files, _L + "post_attention_layernorm.weight",
                            (L, h), transpose=False),
        },
        "final_norm": _direct(files, "model.norm.weight", (h,)),
        "lm_head": lm_head,
    }


def load_llama_params(cfg: llama.LlamaConfig, ckpt_dir: str,
                      mesh: Optional[jax.sharding.Mesh] = None,
                      dtype: Any = None,
                      rules: Optional[Dict] = None) -> Dict[str, Any]:
    """Load an HF-layout Llama safetensors checkpoint into this repo's
    param pytree.

    With ``mesh``, every leaf is built with
    ``jax.make_array_from_callback`` under its logical sharding
    (``param_logical_axes`` + the repo's sharding rules): each
    addressable device's shard is one windowed mmap read + dtype cast —
    a host never materializes more than its own shards.

    ``dtype`` is one type for every leaf (default ``cfg.param_dtype``,
    the trainer's masters) or a tree of types shaped like the params
    (a serving engine's ``llama_infer.storage_dtypes``): each window is
    cast on the host straight to its leaf's type, so a bfloat16
    checkpoint read for serving is never widened on the way.
    """
    files = _FileSet(ckpt_dir)
    specs = _llama_leaf_specs(cfg, files)
    axes = llama.param_logical_axes(cfg)
    is_leaf = lambda x: isinstance(x, _Leaf)
    if not isinstance(dtype, dict):
        one = np.dtype(dtype or cfg.param_dtype)
        dtype = jax.tree.map(lambda _: one, specs, is_leaf=is_leaf)

    def build(leaf: _Leaf, leaf_axes, dt):
        if mesh is None:
            return jnp.asarray(np.asarray(leaf.read(None), dtype=dt))
        sharding = named_sharding(mesh, *leaf_axes, rules=rules)
        return jax.make_array_from_callback(
            leaf.shape, sharding,
            lambda idx: np.asarray(leaf.read(idx), dtype=dt))

    return jax.tree.map(build, specs, axes, dtype, is_leaf=is_leaf)


def save_llama_checkpoint(cfg: llama.LlamaConfig, params: Dict[str, Any],
                          out_dir: str,
                          max_shard_bytes: int = 4 << 30) -> None:
    """Write params back out in HF Llama safetensors layout (per-layer
    tensors, torch (out, in) orientation, size-sharded files + index)."""
    os.makedirs(out_dir, exist_ok=True)
    layers = params["layers"]

    def np_(x) -> np.ndarray:
        x = np.asarray(x)
        if x.dtype == np.dtype(ml_dtypes.bfloat16):
            return x            # keep BF16 storage
        return x

    tensors: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np_(params["embed"]),
        "model.norm.weight": np_(params["final_norm"]),
        "lm_head.weight": np_(params["lm_head"]).T,
    }
    per_layer = {
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "input_layernorm.weight": ("ln1", False),
        "post_attention_layernorm.weight": ("ln2", False),
    }
    if cfg.n_experts:
        for l in range(cfg.n_layers):
            tensors[_L.format(l=l) + "block_sparse_moe.gate.weight"] = (
                np_(layers["router"][l]).T)
            for e in range(cfg.n_experts):
                base = _L.format(l=l) + f"block_sparse_moe.experts.{e}."
                tensors[base + "w1.weight"] = np_(layers["wg"][l, e]).T
                tensors[base + "w3.weight"] = np_(layers["wi"][l, e]).T
                tensors[base + "w2.weight"] = np_(layers["wd"][l, e]).T
    else:
        per_layer.update({
            "mlp.gate_proj.weight": ("wg", True),
            "mlp.up_proj.weight": ("wi", True),
            "mlp.down_proj.weight": ("wd", True),
        })
    for l in range(cfg.n_layers):
        for hf_name, (ours, transpose) in per_layer.items():
            t = np_(layers[ours][l])
            tensors[_L.format(l=l) + hf_name] = t.T if transpose else t

    # size-sharded emission
    shards: List[Dict[str, np.ndarray]] = [{}]
    sizes = [0]
    for name, t in tensors.items():
        if sizes[-1] and sizes[-1] + t.nbytes > max_shard_bytes:
            shards.append({})
            sizes.append(0)
        shards[-1][name] = t
        sizes[-1] += t.nbytes
    if len(shards) == 1:
        write_safetensors(
            os.path.join(out_dir, "model.safetensors"), shards[0],
            metadata={"format": "pt"})
        return
    weight_map: Dict[str, str] = {}
    n = len(shards)
    for i, shard in enumerate(shards):
        fname = f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        write_safetensors(os.path.join(out_dir, fname), shard,
                          metadata={"format": "pt"})
        weight_map.update({k: fname for k in shard})
    with open(os.path.join(out_dir, "model.safetensors.index.json"),
              "w") as f:
        json.dump({"metadata": {"total_size": sum(sizes)},
                   "weight_map": weight_map}, f)


def load_config(ckpt_dir: str) -> llama.LlamaConfig:
    """Build a LlamaConfig from an HF ``config.json`` next to the
    checkpoint (hidden/heads/ffn/rope names translated)."""
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hc = json.load(f)
    n_heads = hc["num_attention_heads"]
    head_dim = hc.get("head_dim") or hc["hidden_size"] // n_heads
    return llama.LlamaConfig(
        vocab_size=hc["vocab_size"], hidden=hc["hidden_size"],
        n_layers=hc["num_hidden_layers"], n_heads=n_heads,
        n_kv_heads=hc.get("num_key_value_heads", n_heads),
        head_dim=head_dim, ffn=hc["intermediate_size"],
        rope_theta=float(hc.get("rope_theta", 500000.0)),
        norm_eps=float(hc.get("rms_norm_eps", 1e-5)),
        max_seq=int(hc.get("max_position_embeddings", 8192)),
        n_experts=int(hc.get("num_local_experts", 0)),
        moe_top_k=int(hc.get("num_experts_per_tok", 2)),
    )


def save_config(cfg: llama.LlamaConfig, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"]
            if not cfg.n_experts else ["MixtralForCausalLM"],
            "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim,
            "intermediate_size": cfg.ffn, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "max_position_embeddings": cfg.max_seq,
            "num_local_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.moe_top_k,
        }, f)

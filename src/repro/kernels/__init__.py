"""Pallas TPU kernels for the compute hot-spots (validated in interpret
mode on CPU against the ref.py jnp oracles; native lowering on TPU).

  registry         enum-dispatched entry points for the worker-step ops
                   (attention / rmsnorm / residual_rmsnorm / ssm_scan),
                   selected by the ``model.kernels`` spec string
  interface        the jax-free half of the registry: KernelType enum,
                   op/variant tables, spec-string parsing
  flash_attention  causal / sliding-window / GQA, online softmax in VMEM;
                   its backward recomputes P block by block (no lq x lk
                   array in HBM)
  rmsnorm          fused single-pass RMSNorm
  residual_rmsnorm fused residual-add + RMSNorm (pre-norm block glue)
  ssm_scan         selective scan with VMEM-resident state carry
  fused_update     DSSP delayed-gradient apply + momentum in one HBM pass
  fused_update_shard  same update over a whole PS shard's packed leaf list
                      (one pallas_call per shard instead of per leaf)
  fused_int8_ef / fused_topk_ef  wire compression + error feedback over
                      the packed (rows, 512) buffer in one VMEM pass

Models go through ``repro.kernels.registry``; the PS/compression path
goes through ``repro.kernels.ops`` (jit wrappers + custom_vjp).

Submodules load lazily (PEP 562) so that ``repro.kernels.interface``
— which the import-light spec layer uses to validate ``model.kernels``
— can be imported without pulling in jax.
"""

import importlib

_SUBMODULES = frozenset({
    "ops", "ref", "interface", "registry", "flash_attention", "rmsnorm",
    "residual_rmsnorm", "ssm_scan", "fused_update", "fused_compress",
})

#: function re-exports kept from the eager-import era (name -> submodule;
#: names that collide with a submodule resolve to the submodule above).
_FUNCS = {
    "flash_attention_fwd": "flash_attention",
    "fused_int8_ef": "fused_compress",
    "fused_topk_ef": "fused_compress",
    "fused_update_shard": "fused_update",
    "pack_shard": "fused_update",
    "unpack_shard": "fused_update",
}

__all__ = sorted(_SUBMODULES | set(_FUNCS))


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    if name in _FUNCS:
        mod = importlib.import_module(f"{__name__}.{_FUNCS[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return __all__

"""Pallas TPU kernels and the planned GEMM / attention dispatch."""


def _compiler_params(dimension_semantics, vmem_bytes: int):
    """Mosaic compiler params of every ``pallas_call``: the grid's
    dimension semantics and a scoped VMEM limit derived from the plan's
    modeled working set ``vmem_bytes``
    (:func:`repro.core.memory_model.vmem_limit_bytes`)."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.core.memory_model import vmem_limit_bytes
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes(vmem_bytes))


def acc_dtype(in_dtype):
    """The paper's accumulation rule, shared by every GEMM kernel:
    int8 operands accumulate in int32, floats in fp32."""
    import jax.numpy as jnp
    return jnp.int32 if in_dtype == jnp.int8 else jnp.float32

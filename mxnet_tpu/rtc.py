"""Runtime kernel compilation — the Pallas bridge.

Reference parity: python/mxnet/rtc.py (CudaModule/CudaKernel: compile
CUDA source with NVRTC at runtime and launch on NDArrays,
include/mxnet/rtc.h).

TPU-native substitution: the runtime-kernel mechanism on TPU is
**Pallas** — Python kernel functions compiled by Mosaic at trace time.
``PallasModule`` gives the rtc surface over it: wrap a Pallas kernel
function and launch it on NDArrays.  CUDA source strings are not
translatable; ``CudaModule`` raises with guidance.
"""
from __future__ import annotations

from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = ["CudaModule", "PallasModule"]


class CudaModule:
    """Reference rtc.py:CudaModule — CUDA source has no TPU backend."""

    def __init__(self, source, options=(), exports=()):
        raise MXNetError(
            "CudaModule compiles CUDA C++ with NVRTC, which has no TPU "
            "analog; write the kernel as a Pallas function and wrap it "
            "in mxnet_tpu.rtc.PallasModule (see "
            "mxnet_tpu/ops/flash_attention.py for a full example)")


class PallasModule:
    """Launch a Pallas kernel on NDArrays (the TPU rtc).

    kernel_fn: a pallas kernel ``(in_ref..., out_ref...) -> None``.
    out_shapes: list of (shape, dtype) for the outputs.

        mod = PallasModule(my_kernel, [( (128, 128), "float32" )])
        y = mod(x)                      # NDArray in, NDArray out
    """

    def __init__(self, kernel_fn, out_shapes, grid=None, interpret=None):
        import jax

        from jax.experimental import pallas as pl

        self._kernel = kernel_fn
        self._out_shapes = [
            jax.ShapeDtypeStruct(tuple(s), d) for s, d in out_shapes]
        self._grid = grid
        if interpret is None:
            # compiled by Mosaic on a TPU, interpret mode elsewhere
            from .ops import kernel_target

            interpret = not kernel_target.on_tpu()
        self._interpret = interpret
        kwargs = {"grid": grid} if grid else {}
        single = len(self._out_shapes) == 1
        self._call = jax.jit(lambda *xs: pl.pallas_call(
            kernel_fn,
            out_shape=(self._out_shapes[0] if single
                       else self._out_shapes),
            interpret=self._interpret,
            name=getattr(kernel_fn, "__name__", None), **kwargs)(*xs))

    def __call__(self, *inputs):
        arrs = [i._data if isinstance(i, NDArray) else i for i in inputs]
        out = self._call(*arrs)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)

"""TPU accelerator backend."""

import jax

from .abstract_accelerator import Accelerator

# Peak dense bf16 FLOP/s per chip for known TPU generations; used for MFU.
# (v4: 275 TF, v5e: 197 TF, v5p: 459 TF, v6e "Trillium": 918 TF)
_PEAK_TFLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


class TpuAccelerator(Accelerator):
    _name = "tpu"
    _communication_backend_name = "xla"

    def __init__(self):
        self._devices = None

    def devices(self):
        if self._devices is None:
            self._devices = [d for d in jax.devices() if d.platform != "cpu"]
        return self._devices

    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def preferred_matmul_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        # TPUs compute fp16 via fp32/bf16 paths; supported for parity testing.
        return True

    def use_pallas_kernels(self):
        return True

    def peak_flops_per_device(self, dtype=None):
        devs = self.devices()
        if not devs:
            return 0.0
        kind = getattr(devs[0], "device_kind", "").lower()
        for key, val in _PEAK_TFLOPS.items():
            if key in kind:
                return val
        raise ValueError(
            f"no peak FLOP/s known for device kind {kind!r}; add it to "
            f"_PEAK_TFLOPS with its source")


class CpuAccelerator(Accelerator):
    """Host-CPU backend: powers the 8-virtual-device test meshes."""

    _name = "cpu"
    _communication_backend_name = "xla"

    def devices(self):
        return jax.devices()

    def device_name(self, device_index=None):
        if device_index is None:
            return "cpu"
        return f"cpu:{device_index}"

    def preferred_matmul_dtype(self):
        import jax.numpy as jnp

        return jnp.float32

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True

    def use_pallas_kernels(self):
        return False

    def peak_flops_per_device(self, dtype=None):
        return 1e11  # nominal; CPU MFU is not meaningful

"""Device/place management.

Reference analogue: /root/reference/python/paddle/device.py (CPUPlace /
CUDAPlace / set_device).  TPU-native: places map onto jax devices; XLA
owns streams + memory, so a "place" is just a jax.Device handle plus a
default-placement policy — there is no per-op stream scheduling to do.
"""
import jax


class Place:
    def __init__(self, kind, device_id=0):
        self.kind = kind
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.device_id == other.device_id)


def CPUPlace():
    return Place('cpu')


def TPUPlace(device_id=0):
    return Place('tpu', device_id)


# CUDA alias kept for API familiarity; resolves to the accelerator.
def CUDAPlace(device_id=0):
    return Place('tpu', device_id)


def XPUPlace(device_id=0):
    return Place('tpu', device_id)


def _kind_of(dev):
    p = dev.platform.lower()
    if p == 'tpu':
        return 'tpu'
    if p in ('gpu', 'cuda', 'rocm'):
        return 'gpu'
    return 'cpu'


_current_place = None


def set_device(device):
    """set_device('tpu') / 'cpu' / 'tpu:0'."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    kind, _, idx = device.partition(':')
    kind = {'gpu': 'tpu', 'cuda': 'tpu', 'xpu': 'tpu'}.get(kind, kind)
    _current_place = Place(kind, int(idx) if idx else 0)
    return _current_place


def get_device():
    p = get_place()
    return f"{p.kind}:{p.device_id}"


def get_place():
    global _current_place
    if _current_place is None:
        kinds = {_kind_of(d) for d in jax.devices()}
        _current_place = Place('tpu' if 'tpu' in kinds else
                               ('gpu' if 'gpu' in kinds else 'cpu'))
    return _current_place


def NPUPlace(device_id=0):
    """Ascend NPU place — documented non-goal (SURVEY §2); resolves to
    the accelerator like CUDAPlace so place-typed code still runs."""
    return Place('tpu', device_id)


def CUDAPinnedPlace():
    """Pinned-host place. XLA owns host staging buffers on TPU; this is
    an API-compat alias for the CPU place."""
    return Place('cpu')


def is_compiled_with_cuda():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_xpu():
    return any(_kind_of(d) == 'tpu' for d in jax.devices())


def get_cudnn_version():
    """No cuDNN on TPU (reference device.py returns None when CUDA is
    absent — same contract here)."""
    return None


def device_count():
    return len(jax.devices())

from p2p_tpu.obs.timing import StepTimer
from p2p_tpu.utils.images import save_img, to_uint8_img
from p2p_tpu.utils.pool import ImagePool

__all__ = [
    "save_img",
    "to_uint8_img",
    "ImagePool",
    "StepTimer",
]

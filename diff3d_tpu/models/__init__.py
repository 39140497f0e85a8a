"""The denoisers, and the one place that reads which kind a config asks
for: :func:`build_model`."""

from diff3d_tpu.config import Config, ModelConfig, TokenModelConfig
from diff3d_tpu.models.token_denoiser import TokenDenoiser
from diff3d_tpu.models.xunet import XUNet


class UnsupportedModelError(TypeError):
    """An entry point that is written for the X-UNet alone (serving, the
    cascade, checkpoint conversion) was given another kind of model."""


def build_model(cfg: Config):
    """The denoiser ``cfg.model`` describes.  Every model follows the
    forward contract of docs/DESIGN.md §1, so the trainer, the train step
    and the sampler take what this returns without looking at its kind."""
    if isinstance(cfg.model, TokenModelConfig):
        return TokenDenoiser(cfg.model)
    if isinstance(cfg.model, ModelConfig):
        return XUNet(cfg.model)
    raise UnsupportedModelError(
        f"no denoiser for a model config of type "
        f"{type(cfg.model).__name__}")


def build_xunet(cfg: Config, what: str) -> XUNet:
    """:func:`build_model` for the entry points that handle the X-UNet
    alone: ``what`` names the one that refuses."""
    model = build_model(cfg)
    if not isinstance(model, XUNet):
        raise UnsupportedModelError(
            f"{what} supports the X-UNet only; the config describes a "
            f"{type(model).__name__}")
    return model


__all__ = ["XUNet", "TokenDenoiser", "build_model", "build_xunet",
           "UnsupportedModelError"]

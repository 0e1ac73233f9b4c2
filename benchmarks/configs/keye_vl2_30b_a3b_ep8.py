"""Factory of ``keye_vl2_30b_a3b_ep8``: the program's decoder model at the
widths of the configuration's file, as the rank that the file names holds it,
inside a ``TPUEstimator`` with the configuration's AdamW recipe, and where
each of the reference's parameters lives in the program's tree. The recipe,
the rank's share of the experts and the tree's names are
``sdar_30b_a3b_ep8``'s: its functions, not a copy of them (the block
diffusion's noise seed it hands the model is a key the ``KeyeVL2`` reader
does not read)."""

from configs.sdar_30b_a3b_ep8 import build, program_path  # noqa: F401
from configs.sdar_30b_a3b_ep8 import model_config as _model_config


def model_config(cfg):
    """``sdar_30b_a3b_ep8``'s view (the router's full width, the experts
    held, every layer's attention named global for the work module), with
    the config's second name for the experts' count at the published one
    too."""
    out = _model_config(cfg)
    out["num_local_experts"] = out["num_experts"]
    return out

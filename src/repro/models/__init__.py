"""Model zoo: layers, attention variants, MoE, SSD, stacks, assembly."""

from .model import (  # noqa: F401
    active_params,
    chunked_ce,
    count_params,
    forward,
    forward_hidden,
    init_model,
    lm_loss,
    routed_expert_params,
)

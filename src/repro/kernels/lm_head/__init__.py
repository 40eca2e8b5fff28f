from .kernel import (lm_head_builder, lm_head_bwd_dw_builder,
                     lm_head_bwd_dx_builder)
from .ops import lm_head_ce, lm_head_logits
from .ref import lm_head_ce_ref, lm_head_logits_ref, masked_logits_ref

__all__ = ["lm_head_builder", "lm_head_bwd_dx_builder",
           "lm_head_bwd_dw_builder", "lm_head_ce", "lm_head_logits",
           "lm_head_ce_ref", "lm_head_logits_ref", "masked_logits_ref"]

"""Share of the traced stretch of the train window that the device spends
in the flash-attention kernel: the self time of the operations under the
``krnl_flash_attn`` scope, forward and backward (``krnl_flash_attn_bwd``)."""
from bench import spans


def read(ctx):
    return spans.scope_share(ctx, "krnl_flash_attn")

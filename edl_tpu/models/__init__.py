from edl_tpu.obs import trace as _trace

with _trace.package_import(__name__):
    from edl_tpu.models.ctr import CTR_EMBEDDING_RULES, DeepFM, binary_cross_entropy_loss
    from edl_tpu.models.mlp import MLP, LinearRegression
    from edl_tpu.models.gated_delta import (
        GatedDeltaMixer,
        GatedDeltaSpec,
        KimiDeltaMixer,
        KimiDeltaSpec,
    )
    from edl_tpu.models.mamba import Mamba2Mixer, MambaSpec
    from edl_tpu.models.moe import MOE_EP_RULES, DroplessMoE, MoESpec, SwitchMoE
    from edl_tpu.models.short_conv import ShortConvMixer, ShortConvSpec
    from edl_tpu.models.resnet import (
        ResNet,
        ResNet50_vd,
        ResNeXt,
        ResNeXt50_32x4d,
        ResNeXt101_32x16d,
    )
    from edl_tpu.models.decode import greedy_generate, init_cache
    from edl_tpu.models.transformer import (
        ArchSpec,
        BlockDiffusionSpec,
        LatentAttention,
        LatentAttentionSpec,
        MTPSpec,
        SparseAttentionSpec,
        TransformerLM,
    )

__all__ = [
    "MLP",
    "LinearRegression",
    "ResNet",
    "ResNet50_vd",
    "ResNeXt",
    "ResNeXt50_32x4d",
    "ResNeXt101_32x16d",
    "TransformerLM",
    "greedy_generate",
    "init_cache",
    "DeepFM",
    "CTR_EMBEDDING_RULES",
    "binary_cross_entropy_loss",
    "SwitchMoE",
    "DroplessMoE",
    "MoESpec",
    "MOE_EP_RULES",
    "ArchSpec",
    "BlockDiffusionSpec",
    "Mamba2Mixer",
    "MambaSpec",
    "GatedDeltaMixer",
    "GatedDeltaSpec",
    "ShortConvMixer",
    "ShortConvSpec",
    "SparseAttentionSpec",
    "KimiDeltaMixer",
    "KimiDeltaSpec",
    "LatentAttention",
    "LatentAttentionSpec",
    "MTPSpec",
]

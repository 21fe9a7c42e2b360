from .boring import BoringModel, BoringDataModule, XORModel, XORDataModule
from .data_text import ByteLMDataModule, decode_bytes
from .exaone_moe import ExaoneMoE, ExaoneMoEConfig
from .sarvam_mla import SarvamMLA, SarvamMLAConfig
from .generate import decode_step, generate, init_kv_cache, prefill
from .gpt import (
    GPT,
    GPTConfig,
    SyntheticLMDataModule,
    add_lora_adapters,
    extract_lora,
    merge_lora,
    synthetic_lora_adapter,
)
from .mnist import MNISTClassifier, MNISTDataModule
from .quant import is_quantized, quantize_decode_params
from .resnet import ResNet, CIFARDataModule
from .vit import ViT, ViTConfig

__all__ = [
    "decode_step",
    "generate",
    "init_kv_cache",
    "prefill",
    "BoringModel",
    "BoringDataModule",
    "ByteLMDataModule",
    "decode_bytes",
    "XORModel",
    "XORDataModule",
    "MNISTClassifier",
    "MNISTDataModule",
    "GPT",
    "GPTConfig",
    "ExaoneMoE",
    "ExaoneMoEConfig",
    "SarvamMLA",
    "SarvamMLAConfig",
    "SyntheticLMDataModule",
    "add_lora_adapters",
    "extract_lora",
    "merge_lora",
    "synthetic_lora_adapter",
    "ResNet",
    "CIFARDataModule",
    "ViT",
    "ViTConfig",
    "is_quantized",
    "quantize_decode_params",
]

"""Model zoo: flax implementations annotated for mesh sharding.

``Llama`` is the RMSNorm / RoPE / SwiGLU decoder; its config also
expresses OLMoE-1B-7B (``LlamaConfig.olmoe_1b_7b``), whose routed
experts are ``ops/moe.py::routed_ffn`` (dropless, top-k).
``NemotronH`` is the layer-typed stack, one mixer a block by a pattern
over ``M`` (Mamba-2, ``ops/ssm.py``), ``E`` (sigmoid-routed relu^2
experts through the same ``routed_ffn``, of which a share may be held,
plus a shared expert) and ``*`` (grouped-query attention); its config
expresses NVIDIA-Nemotron-3-Nano-30B-A3B. ``JoyAI`` is the
DeepSeek-V3-shaped decoder (latent attention through ``ops/mla.py``, a
leading dense layer, sigmoid-routed SwiGLU experts with a shared one
through ``routed_ffn``, a multi-token-prediction module and its second
loss); its config expresses JoyAI-LLM-Flash. ``Zaya`` is the CCA
decoder (compressed convolutional attention through ``ops/cca.py``, a
top-1 MLP router whose state runs from layer to layer and hands its
routes to ``ops/moe.py::routed_experts``, scaled residuals, a tied
table); its config expresses ZAYA1-8B. ``SmallThinker`` is the
window/global decoder (three windowed RoPE layers to one global layer
without positions through ``causal_attention(window=...)``, a softmax
router that reads the block's input before attention, ReGLU experts
through ``routed_experts``, an untied head); its config expresses
SmallThinker-21BA3B-Instruct. ``KimiLinear`` is the hybrid
linear-attention decoder (three Kimi Delta Attention layers, a chunked
gated delta rule through ``ops/kda.py``, to one latent-attention layer
with no query latent and no positions through ``ops/mla.py``, JoyAI's
routed layer behind a leading dense one); its config expresses
Kimi-Linear-48B-A3B-Instruct. ``Phi4Flash`` is the
decoder-hybrid-decoder (Mamba-1 scans through
``ops/mamba1.py::mamba1_scan`` and differential attention through
``ops/attention.py::differential_attention``, windowed, in a
self-decoder; gated memory units and cross attention that read one
earlier layer's scan and one's K and V in a cross-decoder; LayerNorm, a
tied table); its config expresses Phi-4-mini-flash-reasoning.
``Laguna`` is the window/full decoder whose layers differ in head count
(48 query heads on a full layer, 64 on a sliding one under a 512-key
window, each layer reading its own entry of the published lists; YaRN on
half of a full layer's lanes and plain RoPE on a sliding layer's, two
position tables in one stack; a sigmoid gate a head on the core's
output; JoyAI's routed layer behind a leading dense one); its config
expresses Laguna-XS.2.
``Ouro`` is the looped decoder (one stack of four-norm blocks run
``ut_steps`` times on one parameter tree under ``nn.scan``, the final
norm, an exit gate and the untied head after every pass, a loss weighted
row by row by the learned exit distribution through
``gpt2.chunked_cross_entropy_rows``); its config expresses Ouro-2.6B.
``Granite`` is the Mamba-2 / attention hybrid under Granite's four
multipliers (``NemotronH``'s ``Mamba2Mixer`` at one group and chunk 256,
nine layers in ten, grouped-query attention without positions at the
scale ``attention_multiplier``, ``Phi4Flash``'s fused SwiGLU MLP in every
block, a tied table read under ``embedding_multiplier`` and
``logits_scaling``, the blocks recomputed with the scan's results kept by
name); its config expresses granite-4.0-h-micro.
``Qwen3Next`` is the Gated DeltaNet / gated attention hybrid (three
gated delta rules with a decay a head, 16 key heads under 32 value
heads, through ``ops/kda.py::gdn_scan`` to one grouped-query softmax
layer at head width 256 with per-head zero-centred q/k norms, a quarter
of the lanes rotated and an elementwise sigmoid gate on the core's
output; every block a 512-expert top-10 softmax router through
``routed_ffn`` beside a shared expert under a sigmoid gate; zero-centred
RMSNorm throughout, an untied head); its config expresses
Qwen3-Next-80B-A3B-Instruct.
``MoETransformer`` is the older top-1, capacity-dropping switch model
on GPT-2 blocks, which goes when the dropless path runs under ``ep``
(ROADMAP C5)."""

from ray_tpu.models.gpt2 import GPT2, GPT2Config
from ray_tpu.models.granite import Granite, GraniteHybridConfig
from ray_tpu.models.joyai import JoyAI, JoyAIConfig
from ray_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig
from ray_tpu.models.laguna import Laguna, LagunaConfig
from ray_tpu.models.llama import Llama, LlamaConfig
from ray_tpu.models.moe import MoEConfig, MoETransformer
from ray_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from ray_tpu.models.ouro import Ouro, OuroConfig
from ray_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
from ray_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from ray_tpu.models.resnet import ResNet, ResNet50Config
from ray_tpu.models.smallthinker import SmallThinker, SmallThinkerConfig
from ray_tpu.models.vit import ViT, ViTConfig
from ray_tpu.models.zaya import Zaya, ZayaConfig

__all__ = [
    "GPT2", "GPT2Config", "Granite", "GraniteHybridConfig", "JoyAI",
    "JoyAIConfig", "KimiLinear", "KimiLinearConfig", "Laguna", "LagunaConfig", "Llama", "LlamaConfig",
    "MoETransformer", "MoEConfig", "NemotronH", "NemotronHConfig", "Ouro",
    "OuroConfig", "Phi4Flash", "Phi4FlashConfig", "Qwen3Next",
    "Qwen3NextConfig",
    "ResNet", "ResNet50Config", "SmallThinker", "SmallThinkerConfig", "ViT",
    "ViTConfig", "Zaya", "ZayaConfig",
]

"""deepseek-v2-236b [moe] — MLA + fine-grained MoE (arXiv:2405.04434). 60L
d_model=5120 128H, MLA kv_lora=512 q_lora=1536 (d_nope=128, d_rope=64,
d_v=128); 2 shared + 160 routed top-6 experts of d_expert=1536; dense FFN
(12288) at layer 0; vocab=102400. Decode uses the absorbed-MLA cache.

Routing as published (``MoECfg``): softmax scores, ``group_limited_greedy``
over ``n_group=8`` groups of 20 experts keeping ``topk_group=3``, gates
not renormalised (``norm_topk=False``) and scaled by ``routed_scale=16``.
Rope as published: YaRN (``rope_scaling``: factor 40 over an original
4096 positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
0.707), so the softmax scale is ``192**-0.5 * mscale(0.707)**2``."""

from repro.models.config import ArchConfig, MLACfg, MoECfg, YarnCfg

CONFIG = ArchConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    d_head=128,
    d_ff=12288,                    # dense FFN width (layer 0)
    vocab=102400,
    rope_scaling=YarnCfg(factor=40.0, original_max_seq=4096, beta_fast=32.0,
                         beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    mla=MLACfg(q_lora=1536, kv_lora=512, d_nope=128, d_rope=64, d_v=128),
    moe=MoECfg(n_routed=160, top_k=6, d_expert=1536, n_shared=2,
               capacity_factor=1.25, chunk=256, n_group=8, topk_group=3,
               norm_topk=False, routed_scale=16.0),
    dense_layers=(0,),
)

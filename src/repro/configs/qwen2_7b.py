"""Qwen2-7B [arXiv:2407.10671]: GQA kv=4, QKV bias.

``qwen2-7b-14l`` is the one-chip serving cut of the same model.  Source:
arXiv:2407.10671 and the Qwen2-7B ``config.json`` on Hugging Face.  The one
key changed is ``n_layers`` 28 -> 14; every width is as published.  It
stands for one of two pipeline stages of a qwen2-7b verifier, the other 14
layers on a second chip.  Arithmetic: 233.1 M parameters per layer plus
2 x 545 M in the untied embedding and head gives 4.35 B parameters, 8.7 GB
in bf16; KV costs 14 layers x 4 KV heads x 128 dims x (K, V) = 56 KiB per
token in f32 pages, 28 KiB in bf16.
"""
import dataclasses

from repro.configs.base import ArchConfig, register

CONFIG = register(
    ArchConfig(
        name="qwen2-7b",
        family="dense",
        n_layers=28,
        d_model=3584,
        n_heads=28,
        n_kv_heads=4,
        d_ff=18_944,
        vocab=152_064,
        head_dim=128,
        rope_theta=1_000_000.0,
        qkv_bias=True,
    )
)

ONE_CHIP = register(dataclasses.replace(CONFIG, name="qwen2-7b-14l", n_layers=14))

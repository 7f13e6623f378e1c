"""Small configurations of each family, in the published keys' form, with
the program fields that run them. Widths are cut here, unlike in the
benchmark's configurations: these only feed tests on the CPU."""

DENSE = {
    "name": "dense-small", "family": "dense",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "initializer_range": 0.02,
    "program": {"arch": "smollm-360m", "fields": {
        "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 160, "vocab_size": 256, "rope_theta": 10000.0,
        "norm_eps": 1e-5, "tie_embeddings": True, "dtype": "float32",
        "remat": True, "use_flash": False}},
    "train": {"batch": 4, "seq": 32, "optimizer": {
        "lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
        "grad_clip": 1.0, "warmup_steps": 100, "total_steps": 10000,
        "min_lr_frac": 0.1}},
    "reference": {"row_block": 2},
}

SSM = {
    "name": "ssm-small", "family": "ssm",
    "d_model": 64, "n_layer": 2, "vocab_size": 250,
    "ssm_cfg": {"d_state": 16, "expand": 2, "headdim": 16, "chunk_size": 8},
    "norm_epsilon": 1e-5, "initializer_range": 0.02,
    "program": {"arch": "mamba2-2.7b", "fields": {
        "family": "ssm", "n_layers": 2, "d_model": 64, "vocab_size": 250,
        "ssm_state": 16, "ssm_expand": 2, "ssm_headdim": 16, "ssm_chunk": 8,
        "norm_eps": 1e-5, "tie_embeddings": True, "dtype": "float32",
        "remat": True, "use_ssd_kernel": False}},
    "train": DENSE["train"] | {"seq": 32},
    "reference": {"row_block": 2, "head_group": 4},
}

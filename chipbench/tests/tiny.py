"""A cell small enough for the CPU, on the same harness and program paths."""

import cells

# set from CPU readings at this size over seeds 0-5: sound runs read at most
# loss 2.2e-4, grad 0.016, update 0.012, grad_diff 0.115, grad_sign 1.2e-3,
# update_sign 6.3e-3 (MoE, f32 weights; at this size a few tokens change
# experts on rounding) and 1.4e-4, 1.8e-3, 0.053, 0.011, 0, 7.7e-3 (dense,
# bf16 weights); the float8 control reads at least 7.1e-5, 0.049, 0.024, 0.31,
# 5.2e-3, 0.035 and 8.7e-4, 0.021, 0.014, 0.18, 3.5e-4, 0.075 on seeds 0-2.
# With bf16 weights this small the change after three steps is bf16 rounding
# either way, so no update limit separates it. The sign limits follow the
# chip cells' rule: lower^0.4 * upper^0.6, upper / 10 where the lower is 0.
LIMITS = {
    True: {"loss": 3e-4, "grad": 0.03, "update": 0.018, "grad_diff": 0.2, "grad_sign": 0.0029,
           "update_sign": 0.018},
    False: {"loss": 5e-4, "grad": 0.006, "update": 0.1, "grad_diff": 0.05, "grad_sign": 3.5e-5,
            "update_sign": 0.03},
}

def config(moe: bool = True) -> dict:
    c = {
        "program_config": "granite_moe_1b_a400m" if moe else "deepseek_7b",
        "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 1, "vocab_size": 300,
        "rope_theta": 10000.0, "tie_word_embeddings": moe,
        "param_dtype": "float32" if moe else "bfloat16", "compute_dtype": "bfloat16",
    }
    if moe:
        c.update({"num_local_experts": 4, "num_experts_per_tok": 2, "moe_capacity_factor": 1.25,
                  "moe_aux_loss_coef": 0.01, "moe_z_loss_coef": 0.001})
    return c


def cell(strategy: str = "ef_allgather", workers: int = 1, moe: bool = True, **traffic) -> cells.Cell:
    t = {"strategy": strategy, "backend": "auto", "bucket_size": 1024, "workers": workers,
         "rows_per_worker": 4, "seq": 64, "optimizer": "sgdm", "lr": 0.01, **traffic}
    return cells.Cell("tiny", workers, config(moe), t, dict(LIMITS[moe]))

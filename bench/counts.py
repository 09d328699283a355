"""The work a federated round needs, counted from the configuration's
shapes: operations for the whole round's share of the chip's peak
(``mfu``), and the bytes the local update and the server fold must move
(their rooflines).  They count what the algorithm requires, not what an
implementation happens to do, so a PR that replaces a kernel is read
against the same numbers.  All counts are per round; ``n_active`` is the
number of clients that trained in it."""
from __future__ import annotations

F32 = 4  # bytes per element of every plane


def lm_param_counts(cfg: dict) -> dict:
    """Parameter counts of a dense decoder with untied embeddings, no
    biases and a non-gated MLP."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    L = cfg["num_hidden_layers"]
    attn = D * H * hd * 2 + D * Hkv * hd * 2
    mlp = 2 * D * F
    norms = (2 * L + 1) * D
    embed = V * D
    matmul = L * (attn + mlp) + V * D  # layers and the unembedding
    return {"total": embed + matmul + norms, "matmul": matmul, "embed": embed}


def lm_round(cfg, batch_size, seq_len, local_steps, n_active) -> dict:
    """6 operations per matmul parameter per token (the embedding lookup
    needs none), plus the attention scores and their weighted sum: 12 per
    layer, head dimension and key position per token, forward and
    backward, counting every key position as the program computes them."""
    counts = lm_param_counts(cfg)
    tokens = n_active * local_steps * batch_size * seq_len
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * seq_len
    flops = (6 * counts["matmul"] + attn) * tokens
    return _planes(counts["total"], local_steps, n_active, flops=flops)


def _planes(P, local_steps, n_active, *, flops) -> dict:
    """Bytes of the two plane layers of FedCM at P parameters:

    - local update, per client step: read x, the gradient and the server
      momentum, write x;
    - server fold, per round: read each active client's change, read and
      write x and the momentum."""
    return {
        "flops": flops,
        "direction_bytes": n_active * local_steps * 4 * P * F32,
        "fold_bytes": (n_active + 4) * P * F32,
    }

"""Operations the model's forward pass needs for one token.

Per layer: the attention projections, the scores and values over the
``context`` positions the token attends to, the router, the shared
experts and the ``top_k`` routed experts the token is sent to.  Capacity
slack and dropped dispatches are not counted, nor is anything the
program computes and throws away.  The output head counts only where the
token's logits are needed.
"""


def cost(cfg, context: int, head: bool):
    """Multiply-adds times two for one token of ``cfg`` (a configuration
    file) attending to ``context`` positions."""
    a = cfg["assumed"]
    d, hd = cfg["hidden_size"], a["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff = cfg["moe_intermediate_size"]
    experts = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    proj = d * h * hd * 2 + d * kv * hd * 2
    attn = 2 * h * hd * context
    moe = d * cfg["n_routed_experts"] + 3 * d * ff * experts
    per_layer = proj + attn + moe
    macs = cfg["num_hidden_layers"] * per_layer
    if head:
        macs += d * cfg["vocab_size"]
    return 2.0 * macs

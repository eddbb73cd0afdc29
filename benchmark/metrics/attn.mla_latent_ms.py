"""Device time a step of latent attention's own work outside its kernels:
the ops under the program's ``mla_kv_down`` (``[c, k_r] = x W_kva`` and
the latent's RMSNorm), ``mla_kv_up`` (``[k_n, v] = c W_kvb``) and
``mla_rope`` (rotary positions on ``q_r`` and ``k_r``, and q and k
assembled per head with ``k_r`` broadcast over the heads) scopes; all
three passes, over the traced slice's whole runs. What absorbing
``W_kvb`` or reading the shared rotary key inside the kernel would save
is here."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(
        ctx, layers.scope_regex("(mla_kv_down|mla_kv_up|mla_rope)"))

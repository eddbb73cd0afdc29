"""Device time a step under the program's ``diffusion_noise`` scope (the
seeded draw of the step's noise on the device, the noised row, the two
copies' ids), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("diffusion_noise"))

"""Share of the roofline of the LoRA tangent kernels (lora_dual_*)."""
from roofline import family_share


def read(run):
    return family_share(run, "lora_dual")

"""A new architecture, as test_layout.py adds one: copied into
``bench/arch/`` it is the model of ``moe_transformer`` with a count of
its own work, which ``work.token_flops`` then gives for its cells."""
from arch.moe_transformer import (Reference, dims, make_weights,  # noqa: F401
                                  program_config)


def token_flops(z, context):
    """No formula of work.py gives this."""
    return 7 * z["L"] + context

from tpuserve.utils.misc import (cdiv, env_flag, round_up, pad_to,
                                 next_power_of_2)

__all__ = ["cdiv", "env_flag", "round_up", "pad_to", "next_power_of_2"]

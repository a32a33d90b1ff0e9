from .transformer import (decode_step, expert_counts, forward_train,
                          grow_decode_cache, hidden_states, init_decode_cache,
                          init_params, prefill)

__all__ = ["decode_step", "expert_counts", "forward_train",
           "grow_decode_cache", "hidden_states", "init_decode_cache",
           "init_params", "prefill"]

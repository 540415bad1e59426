"""Qwen3ForCausalLM: the dense Qwen decoder of ``bench.models._qwen``."""
from bench.models._qwen import *  # noqa: F401,F403

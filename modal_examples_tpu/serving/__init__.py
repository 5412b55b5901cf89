"""Serving: continuous-batching engine, paged KV cache, sampling, OpenAI API.

The TPU-native replacement for the vLLM/SGLang/TRT-LLM engines every
llm-serving example in the reference shells out to (SURVEY.md §2.2).
"""

from . import disagg, speculative
from .engine import LLMEngine, Request, build_engine
from .kv_cache import OutOfPages, PagedKVCache, PageAllocator
from .openai_api import OpenAIServer
from .sampling import SamplingParams, sample

__all__ = [
    "LLMEngine",
    "disagg",
    "OpenAIServer",
    "OutOfPages",
    "PageAllocator",
    "PagedKVCache",
    "Request",
    "SamplingParams",
    "build_engine",
    "sample",
    "speculative",
]

"""Build a tiny *real* HF checkpoint on disk: model weights + trained BPE
tokenizer + chat template + generation config.

This is the fixture behind the real-checkpoint tests: everything a user's
checkpoint dir would contain (config.json, model.safetensors,
generation_config.json, tokenizer.json, tokenizer_config.json), so loading,
EOS resolution, tokenization, chat templating, and detokenization all run
the production code paths — no toy WordLevel shortcuts.
"""

from __future__ import annotations

import json
import os

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "paris is the capital of france",
    "to be or not to be that is the question",
    "a journey of a thousand miles begins with a single step",
    "all that glitters is not gold",
    "the rain in spain stays mainly in the plain",
    "ask not what your country can do for you",
    "hello world this is a tokenizer training corpus",
    "numbers 0 1 2 3 4 5 6 7 8 9 and punctuation . , ! ?",
]

CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "<|{{ message['role'] }}|>{{ message['content'] }}<|eot|>"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)


def train_bpe_tokenizer(vocab_size: int = 384):
    """A real byte-level BPE tokenizer (llama3-style machinery, tiny vocab)."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tk = Tokenizer(models.BPE())
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<|begin|>", "<|eot|>", "<|user|>", "<|assistant|>",
                        "<|system|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False)
    tk.train_from_iterator(CORPUS, trainer)
    return tk


def make_tiny_llama_checkpoint(path: str, *, num_layers: int = 2,
                               hidden_size: int = 64) -> str:
    """Create a complete tiny-llama checkpoint dir; returns ``path``."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    os.makedirs(path, exist_ok=True)
    tk = train_bpe_tokenizer()
    eot = tk.token_to_id("<|eot|>")

    hf_cfg = LlamaConfig(
        vocab_size=tk.get_vocab_size(), hidden_size=hidden_size,
        intermediate_size=hidden_size * 2, num_hidden_layers=num_layers,
        num_attention_heads=4, num_key_value_heads=2, rope_theta=500000.0,
        max_position_embeddings=512, tie_word_embeddings=False,
        bos_token_id=tk.token_to_id("<|begin|>"), eos_token_id=eot,
        attn_implementation="eager")
    torch.manual_seed(1234)
    model = LlamaForCausalLM(hf_cfg).eval()
    model.generation_config.eos_token_id = eot
    model.save_pretrained(path, safe_serialization=True)

    tk.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "<|begin|>",
            "eos_token": "<|eot|>",
            "chat_template": CHAT_TEMPLATE,
            "tokenizer_class": "PreTrainedTokenizerFast",
        }, f)
    return path


def write_wordlevel_tokenizer_dir(path: str, vocab_size: int) -> None:
    """WordLevel tokenizer whose vocab covers the model's sampled ids, so
    random-weight outputs detokenize through the production DecodeStream."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {f"w{i}": i for i in range(vocab_size)}
    tk = Tokenizer(WordLevel(vocab, unk_token="w0"))
    tk.pre_tokenizer = Whitespace()
    tk.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"chat_template": "{% for m in messages %}{{ m['content'] }}"
                                    "{% endfor %}"}, f)

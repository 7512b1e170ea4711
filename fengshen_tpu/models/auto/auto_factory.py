"""Lazy auto registry for fengshen-tpu model families."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Optional

#: model_type → (module, config class, {head: model class}) — names only,
#: imported lazily like the reference's _LazyAutoMapping
#: (reference: fengshen/models/auto/auto_factory.py:553)
MODEL_REGISTRY: dict[str, tuple[str, str, dict[str, str]]] = {
    "llama": ("fengshen_tpu.models.llama", "LlamaConfig",
              {"causal_lm": "LlamaForCausalLM", "base": "LlamaModel"}),
    "ziya_llama": ("fengshen_tpu.models.llama", "LlamaConfig",
                   {"causal_lm": "LlamaForCausalLM"}),
    "gpt2": ("fengshen_tpu.models.gpt2", "GPT2Config",
             {"causal_lm": "GPT2LMHeadModel", "base": "GPT2Model"}),
    "megatron-bert": ("fengshen_tpu.models.megatron_bert",
                      "MegatronBertConfig",
                      {"base": "MegatronBertModel",
                       "pretraining": "MegatronBertForPreTraining",
                       "masked_lm": "MegatronBertForMaskedLM",
                       "sequence_classification":
                           "MegatronBertForSequenceClassification",
                       "token_classification":
                           "MegatronBertForTokenClassification"}),
    "t5": ("fengshen_tpu.models.t5", "T5Config",
           {"base": "T5Model",
            "conditional_generation": "T5ForConditionalGeneration",
            "encoder": "T5EncoderModel"}),
    "bart": ("fengshen_tpu.models.bart", "BartConfig",
             {"base": "BartModel",
              "conditional_generation": "BartForConditionalGeneration"}),
    "roformer": ("fengshen_tpu.models.roformer", "RoFormerConfig",
                 {"base": "RoFormerModel",
                  "masked_lm": "RoFormerForMaskedLM",
                  "sequence_classification":
                      "RoFormerForSequenceClassification"}),
    "albert": ("fengshen_tpu.models.albert", "AlbertConfig",
               {"base": "AlbertModel", "masked_lm": "AlbertForMaskedLM",
                "sequence_classification":
                    "AlbertForSequenceClassification"}),
    "deberta-v2": ("fengshen_tpu.models.deberta_v2", "DebertaV2Config",
                   {"base": "DebertaV2Model",
                    "masked_lm": "DebertaV2ForMaskedLM",
                    "sequence_classification":
                        "DebertaV2ForSequenceClassification"}),
    "longformer": ("fengshen_tpu.models.longformer", "LongformerConfig",
                   {"base": "LongformerModel",
                    "masked_lm": "LongformerForMaskedLM",
                    "sequence_classification":
                        "LongformerForSequenceClassification"}),
    "bert": ("fengshen_tpu.models.bert", "BertConfig",
             {"base": "BertModel", "masked_lm": "BertForMaskedLM"}),
    "pegasus": ("fengshen_tpu.models.pegasus", "PegasusConfig",
                {"conditional_generation":
                     "PegasusForConditionalGeneration"}),
    "zen": ("fengshen_tpu.models.zen", "ZenConfig",
            {"base": "ZenModel",
             "sequence_classification": "ZenForSequenceClassification"}),
    "deltalm": ("fengshen_tpu.models.deltalm", "DeltaLMConfig",
                {"conditional_generation":
                     "DeltaLMForConditionalGeneration"}),
    "zen2": ("fengshen_tpu.models.zen2", "Zen2Config",
             {"base": "Zen2Model", "masked_lm": "Zen2ForMaskedLM",
              "sequence_classification": "Zen2ForSequenceClassification",
              "token_classification": "Zen2ForTokenClassification",
              "question_answering": "Zen2ForQuestionAnswering"}),
    "davae": ("fengshen_tpu.models.davae", "DAVAEConfig",
              {"base": "DAVAEModel"}),
    "gavae": ("fengshen_tpu.models.gavae", "GAVAEConfig",
              {"base": "GAVAEModel"}),
    "ppvae": ("fengshen_tpu.models.ppvae", "PPVAEConfig",
              {"base": "PPVAEModel"}),
    "della": ("fengshen_tpu.models.deepvae", "DellaConfig",
              {"base": "DellaModel"}),
    "transfo-xl-denoise": ("fengshen_tpu.models.transfo_xl_denoise",
                           "TransfoXLDenoiseConfig",
                           {"base": "TransfoXLDenoiseModel"}),
    "transfo-xl-paraphrase": ("fengshen_tpu.models.transfo_xl_paraphrase",
                              "TransfoXLParaphraseConfig",
                              {"base": "TransfoXLParaphraseModel"}),
    "transfo-xl-reasoning": ("fengshen_tpu.models.transfo_xl_reasoning",
                             "TransfoXLReasoningConfig",
                             {"base": "TransfoXLReasoningModel"}),
    "KeyeVL2": ("fengshen_tpu.models.keye", "KeyeConfig",
                {"causal_lm": "KeyeForCausalLM", "base": "KeyeModel"}),
    "afmoe": ("fengshen_tpu.models.trinity", "TrinityConfig",
              {"causal_lm": "TrinityForCausalLM", "base": "TrinityModel"}),
    "kimi_linear": ("fengshen_tpu.models.kimi_linear", "KimiLinearConfig",
                    {"causal_lm": "KimiLinearForCausalLM",
                     "base": "KimiLinearModel"}),
    "sdar_moe": ("fengshen_tpu.models.sdar", "SdarConfig",
                 {"causal_lm": "SdarForCausalLM", "base": "SdarModel"}),
}


def register_model(model_type: str, module: str, config_cls: str,
                   heads: dict[str, str]) -> None:
    """Extend the registry (the reference's trust-remote-code loader role,
    reference: fengshen/models/auto/dynamic.py:107)."""
    MODEL_REGISTRY[model_type] = (module, config_cls, heads)


def _resolve(model_type: str):
    if model_type not in MODEL_REGISTRY:
        raise KeyError(
            f"unknown model_type {model_type!r}; known: "
            f"{sorted(MODEL_REGISTRY)}")
    module_name, config_name, heads = MODEL_REGISTRY[model_type]
    module = importlib.import_module(module_name)
    return module, config_name, heads


def _model_type_from_path(path: str) -> str:
    cfg_file = os.path.join(path, "config.json") if os.path.isdir(path) \
        else path
    with open(cfg_file) as f:
        raw = json.load(f)
    return raw.get("fengshen_model_type", raw.get("model_type", ""))


class AutoConfig:
    @staticmethod
    def from_pretrained(path: str, **kwargs) -> Any:
        model_type = _model_type_from_path(path)
        module, config_name, _ = _resolve(model_type)
        return getattr(module, config_name).from_pretrained(path)

    @staticmethod
    def for_model(model_type: str, **kwargs) -> Any:
        module, config_name, _ = _resolve(model_type)
        return getattr(module, config_name)(**kwargs)


class AutoModel:
    @staticmethod
    def from_config(config: Any, head: str = "base") -> Any:
        for model_type, (module_name, config_name, heads) in \
                MODEL_REGISTRY.items():
            if type(config).__name__ == config_name and head in heads:
                module = importlib.import_module(module_name)
                return getattr(module, heads[head])(config)
        raise KeyError(
            f"no registered model for config {type(config).__name__} "
            f"with head {head!r}")

    @staticmethod
    def from_pretrained(path: str, head: str = "base") -> tuple[Any, Any]:
        """Returns (model, params) for checkpoints with a converter."""
        model_type = _model_type_from_path(path)
        module, config_name, heads = _resolve(model_type)
        config = getattr(module, config_name).from_pretrained(path)
        if head not in heads:
            raise KeyError(f"model_type {model_type!r} has no head "
                           f"{head!r}; known: {sorted(heads)}")
        model = getattr(module, heads[head])(config)
        params = None
        try:
            convert = importlib.import_module(module.__name__ + ".convert")
        except ModuleNotFoundError:
            return model, params
        try:
            if hasattr(convert, "load_hf_pretrained"):
                _, params = convert.load_hf_pretrained(path, config)
            elif hasattr(convert, "torch_to_params"):
                # generic path: reference-format torch weights in the dir
                # → the family converter (passing the requested head when
                # the converter dispatches on it)
                import inspect

                from fengshen_tpu.utils.convert_common import \
                    load_torch_checkpoint
                state = load_torch_checkpoint(path)
                kwargs = {}
                if "head" in inspect.signature(
                        convert.torch_to_params).parameters:
                    kwargs["head"] = head
                elif head != "base":
                    import logging
                    logging.getLogger("fengshen_tpu").warning(
                        "%s.convert.torch_to_params does not dispatch on "
                        "heads; the tree returned for head=%r may miss "
                        "head weights — flax will error at apply if so. "
                        "Use the family converter directly for full "
                        "control.", module.__name__, head)
                params = convert.torch_to_params(state, config, **kwargs)
        except FileNotFoundError:
            pass  # config-only dir: return a randomly initialisable model
        except ModuleNotFoundError:
            pass  # torch-less install: model with params=None, as before
        return model, params


#: model_type → (module, factory attr) for tokenizers that HF
#: AutoTokenizer cannot resolve (reference:
#: fengshen/models/auto/tokenization_auto.py TOKENIZER_MAPPING)
TOKENIZER_REGISTRY: dict[str, tuple[str, str]] = {
    # char-level Randeng T5: BERT vocab behind a T5 surface
    "megatron_t5": ("fengshen_tpu.models.t5", "T5Tokenizer"),
    "t5_char": ("fengshen_tpu.models.t5", "T5Tokenizer"),
}


class AutoTokenizer:
    """Resolve fengshen-specific tokenizers by the checkpoint's
    config.json (``tokenizer_class``/``fengshen_model_type``/
    ``model_type``), falling through to HF AutoTokenizer."""

    @staticmethod
    def from_pretrained(path: str, **kwargs) -> Any:
        keys = []
        cfg_file = os.path.join(path, "config.json") \
            if os.path.isdir(path) else None
        if cfg_file and os.path.exists(cfg_file):
            with open(cfg_file) as f:
                raw = json.load(f)
            keys = [raw.get("tokenizer_class", ""),
                    raw.get("fengshen_model_type", ""),
                    raw.get("model_type", "")]
        for key in keys:
            if key in TOKENIZER_REGISTRY:
                module_name, attr = TOKENIZER_REGISTRY[key]
                cls = getattr(importlib.import_module(module_name), attr)
                return cls.from_pretrained(path, **kwargs)
        import transformers
        return transformers.AutoTokenizer.from_pretrained(path, **kwargs)

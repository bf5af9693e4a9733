from .exporter import export_hf_checkpoint, export_state_dict
from .importer import config_from_hf, import_state_dict, load_hf_checkpoint
from .pipeline import PipelinedTransformerLM, build_pipeline_model
from .presets import (bailing_hybrid, bert, bloom, build_model, deepseek_v3,
                      falcon_h1,
                      glm5_next, glm_moe_dsa, gpt2,
                      llama2, mimo_v2_flash, mixtral, nemotron_h, opt, ouro,
                      solar_open2, tiny_test, why_not_trained, zaya)
from .t5 import T5Config, T5Model, t5
from .transformer import MuP, TransformerConfig, TransformerLM

__all__ = ["MuP", "TransformerConfig", "TransformerLM", "PipelinedTransformerLM",
           "T5Config", "T5Model", "t5",
           "build_model", "build_pipeline_model", "deepseek_v3", "gpt2", "llama2", "mixtral",
           "bailing_hybrid", "bert", "falcon_h1", "glm5_next", "glm_moe_dsa", "mimo_v2_flash", "zaya", "nemotron_h", "opt", "ouro", "solar_open2", "bloom", "tiny_test", "load_hf_checkpoint",
           "import_state_dict", "config_from_hf", "why_not_trained", "export_state_dict",
           "export_hf_checkpoint"]

"""Model zoo: unified stack (transformer.py) covering dense / MoE / SSM /
hybrid / audio / VLM families, plus the paper's §VI CNNs (cnn.py); the FL
engines build either through ``factory.py``."""
from repro.models import cnn, layers, mamba, moe, rglru, transformer  # noqa: F401

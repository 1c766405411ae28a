from .loader import (
    DataLoader,
    ImageFolderDataset,
    PackedTextDataset,
    TextImageDataset,
    image_to_array,
    random_resized_crop,
)
from .tokenizers import (
    ChineseTokenizer,
    HugTokenizer,
    SimpleTokenizer,
    YttmTokenizer,
    default_bpe_path,
    get_tokenizer,
)
from .webdata import TarImageTextDataset, TarLoader, expand_urls

__all__ = [
    "ChineseTokenizer",
    "DataLoader",
    "HugTokenizer",
    "ImageFolderDataset",
    "PackedTextDataset",
    "SimpleTokenizer",
    "TarImageTextDataset",
    "TarLoader",
    "TextImageDataset",
    "YttmTokenizer",
    "default_bpe_path",
    "expand_urls",
    "get_tokenizer",
    "image_to_array",
    "random_resized_crop",
]

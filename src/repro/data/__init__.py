from repro.data.pipeline import (SyntheticTokenDataset, SyntheticLatentDataset,
                                 SyntheticTextLatentDataset, ShardedLoader)

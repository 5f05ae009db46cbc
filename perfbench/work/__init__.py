"""Operations and bytes of each kernel's work, from the cell's shapes."""

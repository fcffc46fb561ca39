-- define [YEAR] = uniform_int(1999, 2002)
-- define [MONTH] = uniform_int(1, 6)
-- define [DAY] = uniform_int(10, 28)
-- note: TPC-DS draws SDATE from the first half of [YEAR]; the generator's grammar
-- has no date domain, so the date is written from three integer draws.
-- define [CATS] = choice_n(3, 'Books','Children','Electronics','Home','Jewelry','Men','Music','Shoes','Sports','Women')
SELECT i_item_id, i_item_desc, i_category, i_class, i_current_price,
       SUM(cs_ext_sales_price) AS itemrevenue,
       SUM(cs_ext_sales_price) * 100 /
           SUM(SUM(cs_ext_sales_price)) OVER (PARTITION BY i_class)
           AS revenueratio
FROM catalog_sales, item, date_dim
WHERE cs_item_sk = i_item_sk
  AND i_category IN ([CATS])
  AND cs_sold_date_sk = d_date_sk
  AND d_date BETWEEN CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE)
                 AND (CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE) + INTERVAL 30 DAYS)
GROUP BY i_item_id, i_item_desc, i_category, i_class, i_current_price
ORDER BY i_category, i_class, i_item_id, i_item_desc, revenueratio
LIMIT 100
